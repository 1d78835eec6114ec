"""ErasureCode base class — shared logic every matrix-code plugin inherits.

Follows src/erasure-code/ErasureCode.{h,cc}: encode_prepare padding semantics
(SIMD_ALIGN=32, zero-fill the tail of the last data chunks, ErasureCode.cc:
137-172), generic encode via encode_chunks (:174-190), generic decode via
matrix recovery (:198-234), greedy _minimum_to_decode (:89-106), chunk
remapping (:260-279), and profile parsing helpers (:281-329).

The compute path is the batched device kernel: encode_chunks/decode_chunks on
(S, k, B) uint8 arrays lower to one MXU matmul (ceph_tpu.ops.gf_kernel), with
the numpy oracle available for verification (profile runtime=cpu).
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

from ceph_tpu.common import lockdep
from ceph_tpu.gf.matrix import recovery_matrix
from ceph_tpu.ops.dispatch import bucket_stripes
from ceph_tpu.ops.gf_kernel import ec_encode_ref

from .interface import ErasureCodeInterface, ErasureCodeProfile

SIMD_ALIGN = 32  # ErasureCode.h SIMD_ALIGN — chunk padding quantum

#: recovery matrices kept per codec (ErasureCodeIsaTableCache analog);
#: true LRU — a hot mixed-pattern workload evicts one cold entry at a
#: time instead of periodically dropping every matrix at once
DECODE_CACHE_CAP = 256

#: erasure patterns per stacked decode table before the table is
#: RETIRED and a fresh generation starts: bounds both the table's
#: host+device memory and the jit signature's table axis on long-lived
#: daemons with churning shard membership.  In-flight batches keep
#: their captured (generation-keyed) table alive; the engine key
#: carries the generation, so cross-generation requests never share a
#: batch and every stripe's pattern index stays valid for the table it
#: was registered against.
PATTERN_TABLE_CAP = 512


class ErasureCode(ErasureCodeInterface):
    """Systematic GF(2^8) matrix code driven by a (k+m, k) generator matrix.

    Subclasses set self.k, self.m and implement _build_generator() returning the
    generator matrix (identity on top).  Everything else — padding, batched
    device encode, decode-by-inversion with an LRU recovery-matrix cache
    (ErasureCodeIsaTableCache analog) — lives here.
    """

    #: MDS codecs with batched encode_chunks/decode_chunks can be laid
    #: out striped for range rmw (ECUtil stripe math); non-MDS codecs
    #: (shec, lrc) fall back to whole-object writes
    supports_rmw_striping = True

    #: codecs whose recovery matrices live at chunk granularity can
    #: submit decodes through the dispatch engine
    #: (submit_decode_chunks); packet-level bitmatrix codecs override
    #: to False and keep the synchronous decode path
    supports_submit_decode = True

    #: erasure patterns a decode table generation holds before it
    #: retires; None = PATTERN_TABLE_CAP (a codec whose per-pattern
    #: operands are large holds fewer)
    pattern_table_cap: int | None = None

    #: profile keys consumed by init (reference: parse() per plugin)
    _PROFILE_KEYS = ("k", "m", "technique", "runtime", "plugin",
                     "crush-failure-domain", "crush-root",
                     "crush-device-class", "directory", "w", "packetsize")

    def __init__(self):
        self.k = 0
        self.m = 0
        self.technique = ""
        self.runtime = "tpu"   # "tpu" (device kernel) or "cpu" (numpy oracle)
        self._generator: np.ndarray | None = None
        self._encoder = None
        #: {mesh: encoder} LRU — submit_chunks through a mesh-sharded
        #: engine uses an encoder whose bit tables are replicated over
        #: that mesh (one broadcast at build, none per flush); keyed by
        #: mesh (not a single slot), so one codec feeding
        #: differently-meshed engines does not rebuild tables on every
        #: alternating submit
        self._mesh_encoders: OrderedDict = OrderedDict()
        self._decode_cache: OrderedDict = OrderedDict()
        #: guards _decode_cache AND the pattern tables: decodes now
        #: submit from many OSD threads through the dispatch engine
        self._decode_lock = lockdep.make_lock("ErasureCode::decode")
        #: t_bucket -> {"gen": generation counter,
        #:              "ids": {(chosen, targets): idx},
        #:              "mats": [(t_bucket, k) uint8 padded matrices],
        #:              "bits": [(k*8, t_bucket*8) uint8 bit matrices],
        #:              "snap": stacked pow2-padded table or None,
        #:              "snap_dev": device-resident copy of snap}
        #: — the heterogeneous-decode pattern registry.  Append-only
        #: WITHIN a generation (indices are stable, so a submitted
        #: stripe's pattern id stays valid however the table grows
        #: behind it); at PATTERN_TABLE_CAP the whole table retires
        #: and a fresh generation starts.
        self._pattern_tables: dict[int, dict] = {}
        #: monotonic generation source for ALL tables of this codec —
        #: never reset (init()'s clear included), so an engine key's
        #: generation component cannot collide across a re-init while
        #: old-generation requests are still queued
        self._pattern_gen = 0
        self._chunk_mapping: list[int] = []

    # -- profile parsing (ErasureCode.cc:281-329 to_int/to_bool) --------------

    @staticmethod
    def to_int(name: str, profile: ErasureCodeProfile, default: int) -> int:
        v = profile.get(name, default)
        try:
            return int(v)
        except (TypeError, ValueError):
            raise ValueError(f"{name}={v!r} is not an integer")

    @staticmethod
    def to_bool(name: str, profile: ErasureCodeProfile, default: bool) -> bool:
        v = str(profile.get(name, default)).lower()
        return v in ("true", "1", "yes")

    def init(self, profile: ErasureCodeProfile) -> None:
        self.parse(profile)
        self._generator = np.asarray(self._build_generator(), dtype=np.uint8)
        assert self._generator.shape == (self.k + self.m, self.k)
        self._encoder = None
        with self._decode_lock:
            self._mesh_encoders.clear()
        with self._decode_lock:
            self._decode_cache.clear()
            self._pattern_tables.clear()

    def parse(self, profile: ErasureCodeProfile) -> None:
        """Subclasses override to parse technique-specific keys; must set k, m."""
        self.k = self.to_int("k", profile, self._default_k())
        self.m = self.to_int("m", profile, self._default_m())
        self.runtime = profile.get("runtime", "tpu")
        if self.k < 1 or self.m < 1:
            raise ValueError(f"k={self.k} m={self.m} must be >= 1")
        unknown = set(profile) - set(self._PROFILE_KEYS)
        if unknown:
            raise ValueError(f"unknown profile keys {sorted(unknown)}")

    def _default_k(self) -> int:
        return 7

    def _default_m(self) -> int:
        return 3

    def _build_generator(self) -> np.ndarray:
        raise NotImplementedError

    @property
    def generator(self) -> np.ndarray:
        assert self._generator is not None, "init() not called"
        return self._generator

    # -- chunk geometry -------------------------------------------------------

    def get_chunk_count(self) -> int:
        return self.k + self.m

    def get_data_chunk_count(self) -> int:
        return self.k

    def get_alignment(self) -> int:
        """Bytes the object must pad to before splitting into k chunks."""
        return self.k * SIMD_ALIGN

    def get_chunk_size(self, stripe_width: int) -> int:
        """ErasureCodeJerasure::get_chunk_size semantics: pad the object to the
        alignment quantum, then divide by k."""
        alignment = self.get_alignment()
        padded = (stripe_width + alignment - 1) // alignment * alignment
        return padded // self.k

    # -- minimum_to_decode (ErasureCode.cc:89-106) ----------------------------

    def minimum_to_decode(self, want_to_read: set, available: set) -> set:
        if want_to_read <= available:
            return set(want_to_read)
        if len(available) < self.k:
            raise IOError(
                f"cannot decode {sorted(want_to_read)}: only "
                f"{len(available)} of k={self.k} chunks available")
        return set(sorted(available)[:self.k])

    # -- encode (ErasureCode.cc:137-190) --------------------------------------

    def encode_prepare(self, data: bytes) -> np.ndarray:
        """Pad + split into (k, chunk) uint8 — zero-fill tail chunks
        (ErasureCode.cc:137-172)."""
        chunk = self.get_chunk_size(len(data))
        padded = np.zeros(self.k * chunk, dtype=np.uint8)
        padded[:len(data)] = np.frombuffer(data, dtype=np.uint8)
        return padded.reshape(self.k, chunk)

    def encode(self, want_to_encode: set, data: bytes) -> dict:
        chunks = self.encode_prepare(data)
        parity = np.asarray(self.encode_chunks(chunks[None]))[0]
        allc = {i: chunks[i].tobytes() for i in range(self.k)}
        allc.update({self.k + i: parity[i].tobytes() for i in range(self.m)})
        return {i: allc[i] for i in want_to_encode}

    def encode_chunks(self, data_chunks):
        """(S, k, B) uint8 -> (S, m, B) uint8 on the selected runtime.

        runtime "tpu" runs the batched MXU kernel, "native" the in-repo
        single-core C SIMD encode (the ISA-L-class plugin proper — same
        role as the reference's isa plugin on hosts without the device),
        and "cpu" the numpy oracle (verification)."""
        coding = self.generator[self.k:]
        if self.runtime == "cpu":
            return ec_encode_ref(coding, np.asarray(data_chunks))
        if self.runtime == "native":
            from ceph_tpu.native import ec_encode_native
            return ec_encode_native(coding, np.asarray(data_chunks))
        if self._encoder is None:
            from ceph_tpu.ops.gf_kernel import make_encoder
            self._encoder = make_encoder(coding)
        return self._encoder(np.asarray(data_chunks, dtype=np.uint8))

    #: distinct meshes whose encoders one codec keeps resident
    MESH_ENCODER_CAP = 4

    def _encoder_for_mesh(self, mesh):
        """Encoder with bit tables replicated over ``mesh`` (the
        engine's placement mesh) — a mesh-sharded batch then meets
        mesh-resident tables instead of a per-flush broadcast.
        Mesh-keyed true LRU (meshes hash by value, so a hot-reload's
        rebuilt-but-equal mesh hits the same entry), the OrderedDict
        idiom the recovery caches use; the build (bit tables +
        broadcast) runs OUTSIDE the lock, a racing duplicate is
        idempotent."""
        with self._decode_lock:
            enc = self._mesh_encoders.get(mesh)
            if enc is not None:
                self._mesh_encoders.move_to_end(mesh)
                return enc
        from ceph_tpu.ops.gf_kernel import make_encoder
        enc = make_encoder(self.generator[self.k:], mesh=mesh)
        with self._decode_lock:
            self._mesh_encoders[mesh] = enc
            self._mesh_encoders.move_to_end(mesh)
            while len(self._mesh_encoders) > self.MESH_ENCODER_CAP:
                self._mesh_encoders.popitem(last=False)
        return enc

    def submit_chunks(self, engine, data_chunks, cost_tag=None):
        """Submit an (S, k, B) encode through a dispatch engine
        (ops.dispatch): returns a DispatchFuture of the (S, m, B)
        parity.  Concurrent submits against the same codec and chunk
        width coalesce on the stripe axis into one device call; the
        engine's zero-stripe padding is bit-exact here because the code
        is linear (zeros encode to zeros).  On a mesh-sharded engine
        the coalesced batch additionally splits its stripe axis across
        the mesh (host runtimes opt out — sharding a batch a numpy fn
        would immediately gather back is pure overhead).  ``cost_tag``
        is the (tenant, dmclock class) pair the tenant device-time
        ledger attributes this request's stripe share to."""
        # analysis: allow[blocking] -- chunk input is host bytes/numpy by API contract
        data = np.asarray(data_chunks, dtype=np.uint8)
        key = ("ec_encode", id(self), self.k, self.m, data.shape[-1],
               self.runtime)
        cache_entries = None
        fn = self.encode_chunks
        place = False
        fallback = None
        if type(self).encode_chunks is ErasureCode.encode_chunks:
            # bit-exact host oracle for the engine's failure ladder
            # (zeros-pad linearity holds for the oracle exactly as for
            # the kernel).  Only the base dense encode qualifies: an
            # overriding codec's packet/layered pipeline has no dense
            # generator equivalent, so it keeps retry-only recovery.
            coding = self.generator[self.k:]

            def fallback(batch, _c=coding):
                # analysis: allow[blocking] -- host-oracle fallback receives the engine's rebuilt HOST batch (numpy), never a device value
                return ec_encode_ref(_c, np.asarray(batch))
        if self.runtime == "tpu":
            from ceph_tpu.ops.gf_kernel import _jit_entries
            cache_entries = _jit_entries
            # mesh placement only fits the BASE dense-matrix encode:
            # codecs overriding encode_chunks (packet-level bitmatrix)
            # run their own host/packet pipelines a sharded batch would
            # break or gather back
            if type(self).encode_chunks is ErasureCode.encode_chunks:
                place = True
                mesh = engine.placement_mesh()
                if mesh is not None:
                    fn = self._encoder_for_mesh(mesh)
        return engine.submit(key, fn, data,
                             label="ec_encode",
                             cache_entries=cache_entries, place=place,
                             fallback=fallback, cost_tag=cost_tag)

    # -- decode (ErasureCode.cc:198-234 / ErasureCodeIsa.cc:150-310) ----------

    def _recovery_cached(self, key, build) -> np.ndarray:
        """The LRU protocol both recovery caches share (base and the
        packet-level bitmatrix override): move-to-end on hit, evict the
        single least-recent entry past the cap — a hot mixed-pattern
        workload never loses its whole working set at once.  ``build``
        (the matrix inversion) runs OUTSIDE the lock; a racing
        duplicate computation is idempotent."""
        with self._decode_lock:
            mat = self._decode_cache.get(key)
            if mat is not None:
                self._decode_cache.move_to_end(key)
                return mat
        mat = build()
        with self._decode_lock:
            self._decode_cache[key] = mat
            self._decode_cache.move_to_end(key)
            while len(self._decode_cache) > DECODE_CACHE_CAP:
                self._decode_cache.popitem(last=False)
        return mat

    def _recovery(self, chosen: tuple, targets: tuple) -> np.ndarray:
        """LRU-cached recovery matrix (ErasureCodeIsaTableCache
        analog)."""
        return self._recovery_cached(
            (chosen, targets),
            lambda: recovery_matrix(self.generator, list(chosen),
                                    list(targets)))

    def decode_chunks(self, chosen, chunks, targets):
        """chunks: (S, k, B) uint8 rows ``chosen`` -> (S, len(targets), B)."""
        rmat = self._recovery(tuple(chosen), tuple(targets))
        if self.runtime == "cpu":
            return ec_encode_ref(rmat, np.asarray(chunks))
        if self.runtime == "native":
            from ceph_tpu.native import ec_encode_native
            return ec_encode_native(rmat, np.asarray(chunks))
        from ceph_tpu.ops.gf_kernel import ec_encode_jax
        return ec_encode_jax(rmat, np.asarray(chunks, dtype=np.uint8))

    # -- heterogeneous-matrix batched decode (the submit path) ----------------

    def _target_bucket(self, t: int) -> int:
        """Pad target-row counts up to a per-codec constant: every
        pattern with <= m targets (the only counts a degraded read or
        recovery pull can produce) shares ONE bucket, so 1-erasure and
        2-erasure decodes coalesce into the same device call.  Wider
        requests (generic decode_chunks callers) get their own pow-2
        bucket."""
        return bucket_stripes(max(t, self.m, 1))

    def _register_pattern(self, chosen: tuple, targets: tuple
                          ) -> tuple[int, int, dict]:
        """(pattern index, t_bucket, table) for an erasure pattern,
        creating the padded recovery matrix + bit matrix on first
        sight.  The returned TABLE is what the submitter must capture
        (and key its engine requests by ``table["gen"]``): a cap-full
        table retires wholesale, and an in-flight stripe's index is
        only meaningful against the generation it registered with.
        Raises ValueError when the chosen rows are singular."""
        tb = self._target_bucket(len(targets))
        with self._decode_lock:
            tab = self._pattern_tables.get(tb)
            if tab is not None:
                idx = tab["ids"].get((chosen, targets))
                if idx is not None:
                    return idx, tb, tab
        # matrix inversion + bit expansion OUTSIDE the lock; a racing
        # duplicate registration is resolved below
        rmat = self._recovery(chosen, targets)
        padded = np.zeros((tb, self.k), dtype=np.uint8)
        padded[:len(targets)] = rmat
        from ceph_tpu.gf.tables import bit_matrix
        bits = bit_matrix(padded)
        with self._decode_lock:
            tab = self._pattern_tables.get(tb)
            cap = self.pattern_table_cap or PATTERN_TABLE_CAP
            if tab is None or len(tab["mats"]) >= cap:
                # retire the full table: new submissions start a fresh
                # generation (new engine key); in-flight batches keep
                # their captured table object alive until delivered
                self._pattern_gen += 1
                tab = {"gen": self._pattern_gen,
                       "ids": {}, "mats": [], "bits": [],
                       "snap": None, "snap_dev": None}
                self._pattern_tables[tb] = tab
            idx = tab["ids"].get((chosen, targets))
            if idx is None:
                idx = len(tab["mats"])
                tab["ids"][(chosen, targets)] = idx
                tab["mats"].append(padded)
                tab["bits"].append(bits)
                tab["snap"] = None       # table grew: re-snapshot
                tab["snap_dev"] = None   # lazily, host and device
            return idx, tb, tab

    def _pattern_snapshot(self, tab: dict, device: bool = False,
                          mesh=None):
        """(stacked pow2-padded bit table (P, k*8, tb*8) int8, padded
        uint8 matrices, live pattern count) for a captured table
        object — the operand the batched kernel gathers from.  Pow-2
        padding with zero matrices bounds the jit cache by the table
        bucket, not the pattern population; a zero matrix decodes
        anything to zeros, and no live stripe ever indexes a padded
        slot.

        ``device=True`` returns a device-RESIDENT table (cached until
        the table grows): the whole point of coalescing is amortizing
        the dispatch boundary, so the table must not be re-uploaded
        host-to-device on every call — the same rule make_encoder
        applies to the encode tables.  ``mesh`` (a mesh-sharded
        engine's placement mesh) places the device table REPLICATED
        over the mesh so the gather kernel meets a sharded batch with
        consistent shardings; the cached copy is keyed to the mesh and
        rebuilt when it changes.  The stack + upload run OUTSIDE
        the codec lock: the table is append-only within a generation,
        so a prefix copy is consistent and covers every pattern index
        any in-flight batch can carry (indices are assigned before
        submit); a concurrent append just leaves the cached snapshot
        for the next caller to rebuild."""
        with self._decode_lock:
            host = tab["snap"]
            dev = tab["snap_dev"]
            if tab.get("snap_dev_mesh") != mesh:
                dev = None   # mesh changed: re-place (VALUE equality —
                # a hot-reload rebuilds an equal Mesh object, and the
                # cached table placed on it is still the right one)
            mats = list(tab["mats"])
            if host is not None and (dev is not None or not device):
                return (dev if device else host), mats, len(mats)
            bits = list(tab["bits"])
        n = len(bits)
        if host is None:
            host = np.zeros((bucket_stripes(max(n, 1)),)
                            + bits[0].shape, dtype=np.int8)
            host[:n] = np.stack(bits)
        if device:
            import jax
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                dev = jax.device_put(
                    host, NamedSharding(mesh, PartitionSpec()))
            else:
                dev = jax.device_put(host)
        with self._decode_lock:
            if len(tab["bits"]) == n:    # still current: cache it
                tab["snap"] = host
                if device:
                    tab["snap_dev"] = dev
                    tab["snap_dev_mesh"] = mesh
        return (dev if device else host), mats, n

    def _decode_batch_fn(self, tab: dict, tb: int, stats=None):
        """The engine-side fn for one table generation: decodes a
        coalesced (S, k, B) batch whose stripes may span MANY erasure
        patterns (pattern index per stripe in the aux array).  The
        TABLE OBJECT is captured, not looked up: a retired generation
        stays alive — and its indices meaningful — for exactly as long
        as batches against it are in flight.  ``stats`` is the
        DecodeDispatchStats sink the heterogeneity sample lands in —
        the submitting engine's own sink, so a privately-instrumented
        engine sees its patterns histogram populated."""
        def fn(data, pidx):
            # the pattern-heterogeneity sample reads pidx host-side (it
            # is tiny); the copy feeding the KERNEL stays as the engine
            # delivered it — on a mesh-sharded engine that is a sharded
            # device array gathered per-stripe on every chip
            host_pidx = np.asarray(pidx)
            uniq = np.unique(host_pidx)
            device = self.runtime not in ("cpu", "native")
            mesh = getattr(getattr(data, "sharding", None), "mesh", None) \
                if device else None
            snap, mats, live = self._pattern_snapshot(
                tab, device=device, mesh=mesh)
            if stats is not None:
                stats.record_patterns(int(uniq.size), live)
            if not device:
                if self.runtime == "native":
                    from ceph_tpu.native import ec_encode_native as enc
                else:
                    enc = ec_encode_ref
                return self._host_pattern_decode(enc, mats, host_pidx,
                                                 data, tb)
            from ceph_tpu.ops.gf_kernel import ec_decode_batched
            return ec_decode_batched(snap, pidx, data, k=self.k, t=tb)
        return fn

    @staticmethod
    def _host_pattern_decode(enc, mats, host_pidx, data, tb):
        """Group a coalesced decode batch by pattern index and rebuild
        each group with its padded recovery matrix — THE host decode
        semantics, shared by the cpu-runtime branch of
        ``_decode_batch_fn`` and the engine's fallback oracle.  One
        copy on purpose: the two callers must stay byte-for-byte
        equivalent or fallback-vs-device bit-exactness silently
        breaks on the decode channel."""
        out = np.zeros((data.shape[0], tb, data.shape[-1]),
                       dtype=np.uint8)
        for p in np.unique(host_pidx):
            rows = np.nonzero(host_pidx == p)[0]
            out[rows] = np.asarray(enc(mats[int(p)], data[rows]))
        return out

    def _decode_fallback_fn(self, tab: dict, tb: int):
        """Bit-exact host oracle for one decode table generation — the
        engine's failure ladder runs it when the device path stays
        broken: group the coalesced batch by pattern index and rebuild
        each group with its padded recovery matrix through
        ``ec_encode_ref`` (exactly the cpu-runtime branch of
        ``_decode_batch_fn``, which PR 4's tests pin bit-identical to
        the batched kernel)."""
        def fb(data, pidx):
            host_pidx = np.asarray(pidx)
            data = np.asarray(data)
            _snap, mats, _live = self._pattern_snapshot(tab)
            return self._host_pattern_decode(ec_encode_ref, mats,
                                             host_pidx, data, tb)
        return fb

    def submit_decode_chunks(self, engine, chosen, chunks, targets,
                             cost_tag=None):
        """Submit an (S, k, B) decode through a dispatch engine
        (ops.dispatch): returns a DispatchFuture of the
        (S, len(targets), B) rebuilt rows.  The decode-side twin of
        submit_chunks — but where encodes share one matrix, concurrent
        decodes with DIFFERENT erasure patterns still coalesce into one
        device call: each pattern's recovery matrix (reusing the
        _recovery LRU) is registered in a stacked bit-matrix table, the
        per-stripe pattern index rides the engine's aux channel, and
        the kernel gathers the matrix per stripe
        (gf_kernel.ec_decode_batched).  Raises ValueError synchronously
        when the chosen rows are singular, so callers can fall back to
        the widen-and-regather ladder before anything is queued."""
        data = np.asarray(chunks, dtype=np.uint8)
        chosen = tuple(chosen)
        targets = tuple(targets)
        t = len(targets)
        idx, tb, tab = self._register_pattern(chosen, targets)
        pidx = np.full(data.shape[0] if data.ndim else 1, idx,
                       dtype=np.int32)
        # the table GENERATION is part of the key: requests against a
        # retired table must never share a batch with the generation
        # that replaced it — a pattern index is only meaningful
        # against the table it registered with
        key = ("ec_decode", id(self), self.k, tb, data.shape[-1],
               self.runtime, tab["gen"])
        cache_entries = None
        if self.runtime == "tpu":
            from ceph_tpu.ops.gf_kernel import _decode_jit_entries
            cache_entries = _decode_jit_entries
        # heterogeneity samples land in the ENGINE's stats sink when it
        # is decode-instrumented, falling back to the global decode
        # registry (engines with a plain DispatchStats sink)
        from ceph_tpu.ops import telemetry
        stats = engine.stats if isinstance(
            engine.stats, telemetry.DecodeDispatchStats) \
            else telemetry.decode_dispatch_stats()
        inner = engine.submit(key, self._decode_batch_fn(tab, tb, stats),
                              data, aux=(pidx,), label="ec_decode",
                              cache_entries=cache_entries,
                              place=self.runtime == "tpu",
                              fallback=self._decode_fallback_fn(tab, tb),
                              cost_tag=cost_tag)
        if t == tb:
            return inner
        # the batch computes tb target rows per stripe (the bucket);
        # deliver only this request's real ones.  The wrapper future
        # preserves the engine's delivery order — the slice happens in
        # the inner future's callback, on the completion thread.
        from ceph_tpu.ops.dispatch import DispatchFuture
        outer = DispatchFuture()

        def _slice(f, t=t, outer=outer):
            exc = f.exception()
            if exc is not None:
                outer._deliver(None, exc)
            else:
                # analysis: allow[blocking] -- delivered value is already host numpy (completion thread materialized it)
                outer._deliver(np.asarray(f.result())[:, :t, :], None)

        inner.add_done_callback(_slice)
        return outer

    def decode(self, want_to_read: set, chunks: dict) -> dict:
        available = set(chunks)
        out = {i: chunks[i] for i in want_to_read & available}
        missing = sorted(want_to_read - available)
        if not missing:
            return out
        if len(available) < self.k:
            raise IOError(
                f"cannot decode {missing}: only {len(available)} of "
                f"k={self.k} chunks available")
        chosen = sorted(available)[:self.k]
        arr = np.stack([np.frombuffer(chunks[i], dtype=np.uint8)
                        for i in chosen])
        rebuilt = np.asarray(self.decode_chunks(chosen, arr[None], missing))[0]
        for idx, i in enumerate(missing):
            out[i] = rebuilt[idx].tobytes()
        return out

    # -- chunk remapping (ErasureCode.cc:260-279) -----------------------------

    @staticmethod
    def to_mapping(mapping: str) -> list[int]:
        """Parse a mapping string like "_DDD_DD" — 'D' positions hold chunks,
        other characters are gaps (used by LRC; ErasureCode.cc:260-279)."""
        out = []
        for pos, c in enumerate(mapping):
            if c == "D":
                out.append(pos)
        return out

    def get_chunk_mapping(self) -> list:
        return list(self._chunk_mapping)

    # -- CRUSH rule (ErasureCode.cc:53-72) ------------------------------------

    def create_rule(self, name: str, crush_map) -> int:
        from ceph_tpu.crush.builder import add_simple_rule
        return add_simple_rule(crush_map, -1, 0, "indep")
