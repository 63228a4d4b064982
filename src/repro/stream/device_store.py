"""DeviceShardStore: partition fragments placed on a jax mesh — the
device implementation of :class:`~repro.stream.chunks.PlacementStore`.

"Shards are runs": the external sort's histogram → partition → sort loop
is placement-agnostic, and this store swaps the disk run store's spill
for mesh collectives while the loop stays byte-for-byte the same:

* :meth:`distribute` routes each chunk's rows to their partition's
  *owner device* through one bucket ``all_to_all`` per code word
  (:func:`~repro.core.distributed.make_fragment_placer`) — the
  Stehle & Jacobsen MSB-partition-then-local-sort architecture lifted to
  the mesh level.  The partition→device map is the contiguous,
  order-preserving ``owner(i) = i * D // P``, so the top-k prune (which
  keeps only a partition *prefix*) leaves tail devices fragment-free:
  the histogram decides which devices even participate;
* :meth:`sort_rows` runs each partition through the
  ``DistributedBackend`` pairs path
  (:func:`~repro.core.distributed.make_distributed_sort_pairs`): one
  stable distributed pass chain per active code word, least-significant
  word first, with the row permutation riding the all_to_all buckets as
  the payload — wide (``max_bins_log2=16``) plans by default, the ICI
  scheme.  Narrowed sorts (the shared-prefix cut) work unchanged: the
  distributed pass places the *full* key words by their undetermined
  low field, nothing is reconstructed, so shared high bits survive.

Payload columns (int64 row ids, float64 table columns) cannot ride
device collectives faithfully under x64-off jax; they follow on the host
through the *identical* deterministic placement — the landed tag column
(the collective's own output) indexes them — with a parity assert that
the wire really carried the key words it claims.

The mesh defaults to all local devices on one axis; simulate D host
devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=D``
(set before importing jax).  Axis sizes must be powers of two so
power-of-two padded chunks shard evenly.
"""

from __future__ import annotations

import time
import zlib
from typing import Optional

import numpy as np

from repro.core import faults
from repro.core.faults import CorruptFragmentError, StorePermanentError
from repro.obs import metrics, trace
from repro.stream.chunks import MemoryBudget, PlacementStore

__all__ = ["DeviceShardStore"]

#: padding sentinel rows (all-ones words sort stably after every real row)
_SENTINEL = np.uint32(0xFFFFFFFF)

# the device store's injection sites (chaos-matrix enumerable)
_SITE_PUT = faults.register_site("device_store.put")
_SITE_GET = faults.register_site("device_store.get")
_SITE_DELETE = faults.register_site("device_store.delete")
_SITE_DISTRIBUTE = faults.register_site("device_store.distribute")
_SITE_SORT = faults.register_site("device_store.sort_rows")


def _array_crc(a: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(a).tobytes())


def _flip_byte(a: np.ndarray) -> np.ndarray:
    """A copy with its last byte flipped — the injection registry's
    stand-in for a corrupted host mirror; CRC verification must catch
    it.  (An empty array has no byte to damage and passes through.)"""
    if a.nbytes == 0:
        return a
    b = np.ascontiguousarray(a).copy()
    b.reshape(-1).view(np.uint8)[-1] ^= 0xFF
    return b


class DeviceShardStore(PlacementStore):
    """Partition fragments on a jax mesh; partition sorts run distributed.

    ``mesh`` is a jax mesh with ``axis`` a power-of-two device axis
    (default: one axis over every local device).  Fragments are held as
    the arrays the placement collective landed (plus host payload
    mirrors); :meth:`get` hands them back as host arrays, so the external
    loop's fragment handling is placement-blind.
    """

    #: partition sorts are shard_map collectives — dispatching them from
    #: several host threads at once would interleave collective programs,
    #: so the external loop keeps this store sequential.
    supports_concurrent_sorts = False

    #: each partition sort is a mesh-wide program already sharded over
    #: every device; concatenating partitions into one padded batch would
    #: re-shard them for no new parallelism, so batched dispatch falls
    #: back to the serial per-partition loop here.
    supports_batched_sorts = False

    site_prefix = "device_store"

    #: fragments keep host mirrors, so when the mesh dies permanently
    #: mid-sort the external loop can migrate the remaining partitions to
    #: a disk store and finish bit-exact — graceful degradation instead
    #: of lost work.
    failover_to_disk = True

    def __init__(self, mesh=None, axis: str = "shards", batch: int = 1024,
                 max_bins_log2: int = 16):
        import jax

        from repro.launch.mesh import make_mesh

        if mesh is None:
            n_dev = len(jax.devices())
            mesh = make_mesh((n_dev,), (axis,))
        self.mesh = mesh
        self.axis = axis
        self.batch = batch
        self.max_bins_log2 = max_bins_log2
        self._D = int(mesh.shape[axis])
        assert self._D & (self._D - 1) == 0, (
            f"device axis size {self._D} must be a power of two so "
            "power-of-two padded chunks shard evenly")
        self._next_id = 0
        self._frags: dict = {}       # rid -> tuple of host arrays
        self._crcs: dict = {}        # rid -> per-array CRC32 at put time
        self._frag_dev: dict = {}    # rid -> landing device (None: direct put)
        self.put_log: list = []
        self.get_log: list = []
        #: bytes per successful put/get, aligned with the logs (same
        #: contract as :class:`~repro.stream.chunks.RunStore`)
        self.put_log_bytes: list = []
        self.get_log_bytes: list = []
        #: (fragment id, device index) per placed fragment — the counting
        #: record for "pruned devices receive zero fragments"
        self.device_log: list = []
        self._placers: dict = {}     # (t, W) -> placement collective
        self._sorters: dict = {}     # eff bits -> jitted pairs sort

    # -- capacity accounting --------------------------------------------------

    @property
    def num_devices(self) -> int:
        return self._D

    def owner(self, partition: int, num_partitions: int) -> Optional[int]:
        """Contiguous, order-preserving partition→device map: device ``d``
        owns partitions ``[ceil(d*P/D), ceil((d+1)*P/D))``.  Order
        preservation is what makes the top-k prune a *device* prune — a
        kept partition prefix maps onto a device prefix."""
        assert 0 <= partition < num_partitions
        return partition * self._D // max(num_partitions, 1)

    def nbytes(self) -> int:
        return sum(int(a.nbytes) for arrays in self._frags.values()
                   for a in arrays)

    # -- fragment put/get -----------------------------------------------------

    def put(self, *arrays: np.ndarray,
            partition: Optional[int] = None) -> int:
        """Store one fragment; the landing device is recorded by
        :meth:`distribute` (which placed the rows) — direct puts (result
        runs, interop) have no device.  The host mirrors carry per-array
        CRC32s so :meth:`get` detects a damaged mirror just like the disk
        store detects a torn spill."""
        assert arrays, "a fragment holds at least one array"
        rid = self._next_id
        self._next_id += 1

        def attempt():
            kind = faults.poll(_SITE_PUT)
            held = tuple(np.ascontiguousarray(a) for a in arrays)
            crcs = tuple(_array_crc(a) for a in held)
            if kind == "corrupt":  # CRCs record the intended bytes
                held = held[:-1] + (_flip_byte(held[-1]),)
            return held, crcs

        nbytes = sum(int(np.asarray(a).nbytes) for a in arrays)
        with trace.span("store.put", store=self.site_prefix, rid=rid,
                        bytes=nbytes, arrays=len(arrays)):
            held, crcs = faults.with_retries(_SITE_PUT, attempt)
        self._frags[rid] = held
        self._crcs[rid] = crcs
        self._frag_dev[rid] = None
        self.put_log.append(rid)
        self.put_log_bytes.append(nbytes)
        metrics.counter(f"store.{self.site_prefix}.put.calls").inc()
        metrics.counter(f"store.{self.site_prefix}.put.bytes").inc(nbytes)
        return rid

    def get(self, rid: int, mmap: bool = False):
        assert rid in self._frags, f"no fragment {rid} in store"
        self.get_log.append(rid)
        crc_s = [0.0]  # CRC-verify wall, summed across retry attempts

        def attempt():
            kind = faults.poll(_SITE_GET)
            if kind == "corrupt":
                arrays = self._frags[rid]
                self._frags[rid] = arrays[:-1] + (_flip_byte(arrays[-1]),)
            arrays = self._frags[rid]
            t0 = time.perf_counter()
            for j, crc in enumerate(self._crcs.get(rid, ())):
                got = _array_crc(arrays[j])
                if got != crc:
                    raise CorruptFragmentError(
                        _SITE_GET,
                        f"fragment {rid} array {j}: CRC32 {got:#010x} != "
                        f"recorded {crc:#010x}")
            crc_s[0] += time.perf_counter() - t0
            return arrays

        with trace.span("store.get", store=self.site_prefix,
                        rid=rid) as sp:
            try:
                out = faults.with_retries(_SITE_GET, attempt)
            except BaseException:
                self.get_log_bytes.append(0)
                raise
            nbytes = sum(int(a.nbytes) for a in out)
            sp.set(bytes=nbytes, crc_s=crc_s[0])
        self.get_log_bytes.append(nbytes)
        metrics.counter(f"store.{self.site_prefix}.get.calls").inc()
        metrics.counter(f"store.{self.site_prefix}.get.bytes").inc(nbytes)
        return out

    def delete(self, rid: int) -> None:
        faults.with_retries(
            _SITE_DELETE, lambda: faults.poll(_SITE_DELETE))
        self._frags.pop(rid)
        self._crcs.pop(rid, None)
        self._frag_dev.pop(rid, None)

    def __contains__(self, rid: int) -> bool:
        return rid in self._frags

    def run_ids(self) -> tuple:
        return tuple(sorted(self._frags))

    def close(self) -> None:
        self._frags.clear()
        self._crcs.clear()
        self._frag_dev.clear()

    def fragment_device(self, rid: int) -> Optional[int]:
        """Device a placed fragment landed on (None for direct puts)."""
        return self._frag_dev.get(rid)

    def __len__(self) -> int:
        return len(self._frags)

    # -- the placement collective ---------------------------------------------

    def _placer(self, t: int, num_words: int):
        key = (t, num_words)
        if key not in self._placers:
            import jax

            from repro.core.distributed import make_fragment_placer

            self._placers[key] = jax.jit(make_fragment_placer(
                self.mesh, self.axis, num_words, batch=self.batch))
        return self._placers[key]

    def distribute(self, words: np.ndarray, payloads: tuple,
                   pid: np.ndarray, num_partitions: int) -> list:
        """Place one chunk's rows on their partitions' owner devices via
        one bucket ``all_to_all`` per word column.  Pruned rows
        (``pid < 0``) drop on the wire; per chunk each partition lands at
        most one fragment (its owner is unique), rows in arrival order."""
        n = int(words.shape[0])
        D = self._D
        frag_ids: list = [[] for _ in range(num_partitions)]
        if n == 0:
            return frag_ids
        # byte attribution stays with the nested store.put spans (see
        # RunStore.distribute): this span carries placement shape only
        dist_span = trace.span("store.distribute", store=self.site_prefix,
                               partitions=num_partitions, rows=n,
                               devices=D)
        with dist_span:
            return self._distribute(words, payloads, pid, num_partitions,
                                    frag_ids)

    def _distribute(self, words, payloads, pid, num_partitions, frag_ids):
        import jax.numpy as jnp

        from repro.core.fractal_tree import ceil_log2

        n = int(words.shape[0])
        D = self._D
        # the injection point sits before the collective fires, so a
        # transient retry re-enters a clean distribute (the per-fragment
        # puts retry inside put itself)
        faults.with_retries(
            _SITE_DISTRIBUTE, lambda: faults.poll(_SITE_DISTRIBUTE))
        owner_lut = np.asarray(
            [self.owner(i, num_partitions) for i in range(num_partitions)],
            np.int32)
        dest = np.where(pid >= 0, owner_lut[np.clip(pid, 0, None)],
                        -1).astype(np.int32)
        # pad to the power-of-two ceiling (>= D, so shards stay equal and
        # jit traces stay O(log budget)); padding rows are invalid
        t = max(D, 1 << ceil_log2(n))
        pad = t - n
        words_p = np.concatenate(
            [words, np.full((pad, words.shape[1]), _SENTINEL, np.uint32)]) \
            if pad else words
        dest_p = np.concatenate([dest, np.full((pad,), -1, np.int32)]) \
            if pad else dest
        tag = np.concatenate(
            [np.arange(n, dtype=np.int32), np.full((pad,), -1, np.int32)])

        landed_words, landed_tags = self._placer(t, words.shape[1])(
            jnp.asarray(words_p), jnp.asarray(dest_p), jnp.asarray(tag))
        lw, lt = np.asarray(landed_words), np.asarray(landed_tags)

        for d in range(D):
            tag_d = lt[d * t:(d + 1) * t]
            valid = tag_d >= 0
            if not valid.any():
                continue
            tags = tag_d[valid].astype(np.int64)
            w_d = lw[d * t:(d + 1) * t][valid]
            # the wire must have carried exactly the rows it was asked to
            # place, in arrival order — the device data IS the fragment
            if not np.array_equal(w_d, words[tags]):
                raise CorruptFragmentError(
                    _SITE_DISTRIBUTE,
                    "fragment placement parity violation: landed words "
                    "differ from the chunk rows addressed to this device")
            pids_d = pid[tags]
            for i in np.unique(pids_d):
                sel = pids_d == i
                rid = self.put(
                    w_d[sel], *(p[tags[sel]] for p in payloads),
                    partition=int(i))
                self._frag_dev[rid] = d
                self.device_log.append((rid, d))
                frag_ids[int(i)].append(rid)
        return frag_ids

    # -- the distributed partition sort ---------------------------------------

    def _sorter(self, eff_bits: int):
        if eff_bits not in self._sorters:
            import jax

            from repro.core.distributed import make_distributed_sort_pairs

            self._sorters[eff_bits] = jax.jit(make_distributed_sort_pairs(
                self.mesh, self.axis, eff_bits, num_payloads=1,
                batch=self.batch, max_bins_log2=self.max_bins_log2))
        return self._sorters[eff_bits]

    def sort_rows(self, words: np.ndarray, payloads: tuple, bits: int,
                  sort_bits: int, budget: MemoryBudget, plans=None):
        """Stable distributed sort of one partition on its undetermined
        low ``sort_bits``: per active code word (least-significant first)
        one DistributedBackend pairs run places the word column at its
        exact global ranks with the accumulated row permutation riding as
        the payload — stability across shard boundaries is the backend's
        (device, arrival) tie-break.  Non-device payload columns gather on
        the host by the final permutation (x64-off jax cannot carry
        int64/float64 through collectives faithfully).  ``plans`` (the
        external loop's hoisted local plans) is accepted for protocol
        compatibility and ignored: the distributed program fixes its own
        wide per-word passes (``max_bins_log2``)."""
        m = int(words.shape[0])
        if m <= 1 or sort_bits == 0:
            return words, payloads
        return faults.with_retries(
            _SITE_SORT, lambda: self._sort_rows_once(
                words, payloads, bits, sort_bits, budget))

    def _sort_rows_once(self, words, payloads, bits, sort_bits, budget):
        import jax.numpy as jnp

        from repro.core.fractal_tree import ceil_log2
        from repro.query.codec import word_widths

        m = int(words.shape[0])
        widths = word_widths(bits)
        # word j covers code bits [lo_j, lo_j + widths[j]); only bits
        # below sort_bits are undetermined (same walk as sort_rowids).
        # The width quantizes UP to a multiple of 8: the extra low bits
        # are shared-prefix bits, equal in every row of the partition, so
        # sorting on them changes nothing — while the distributed sort
        # program compiles per width, and partitions arrive with ~any
        # shared-prefix depth; quantizing caps the trace cache at 4
        # programs per word instead of 32
        active, lo = [], bits
        for j, wj in enumerate(widths):
            lo -= wj
            eff = min(sort_bits - lo, wj)
            if eff > 0:
                active.append((j, min(-(-eff // 8) * 8, wj)))
        if not active:
            return words, payloads
        t = max(self._D, 1 << ceil_log2(m))
        padded = words
        if t > m:
            padded = np.concatenate(
                [words, np.full((t - m, words.shape[1]), _SENTINEL,
                                np.uint32)])
        # the sort moment mirrors the disk path's charge model: host
        # padded matrix + device copy + device sorted output — held for
        # the sort's duration so a mid-collective failure releases it
        with budget.hold(padded, padded, padded, *payloads):
            faults.poll(_SITE_SORT)
            wdev = jnp.asarray(padded)
            perm = jnp.arange(t, dtype=jnp.int32)
            for j, eff in reversed(active):
                col = wdev[:, j][perm]  # gather under the current perm
                _, perm, overflow = self._sorter(eff)(col, perm)
                if bool(overflow):
                    # worst-case capacity was provisioned; overflowing it
                    # means the collective itself misbehaved — retrying
                    # the same program is futile
                    raise StorePermanentError(
                        _SITE_SORT,
                        "distributed partition sort overflowed its "
                        "all_to_all buckets despite worst-case capacity")
            rowids = np.asarray(perm)[:m]
            # all-ones sentinels sort after every real row (stability:
            # they also arrive after), so the first m slots are real rows
            assert m == t or int(rowids.max(initial=-1)) < m
            sorted_words = padded[rowids]
            gathered = tuple(np.asarray(p)[rowids] for p in payloads)
        budget.charge(padded, sorted_words, rowids, *payloads, *gathered)
        return sorted_words, gathered
