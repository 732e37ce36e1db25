"""TPU cluster driver — the device-path backend.

Single-controller SPMD driver over a :class:`jax.sharding.Mesh`: the
reference's N socket slaves become N mesh devices, and each collective is
one jitted ``shard_map`` program whose body is an XLA ICI collective
(``ops.collectives``). Where the reference runs log2(n) Kryo-socket
rounds per collective (SURVEY.md section 3b), this backend emits a single
``psum`` / ``psum_scatter`` / ``all_gather`` and lets XLA schedule ICI DMA.

Driver-mode semantics: collective methods take a list of ``n`` per-rank
numpy arrays (the check-suite shape, SURVEY.md section 4), stage them onto
the mesh with the axis sharding, run the jitted collective, and write
results back IN PLACE into the per-rank arrays — matching the reference's
in-place buffer semantics. The per-shard functional layer
(``ops.collectives``) is the API for use inside user jit code.

Uneven ranges and sub-ranges ``[from, to)`` are handled by host-side
packing into equal static blocks padded with the operator identity, so
the jitted core sees only static shapes (XLA requirement).

Precision: device compute uses the operand dtype; 64-bit operands require
``jax.config.jax_enable_x64`` (the differential test rig enables it on
CPU). Without x64, 64-bit operands are rejected rather than silently
downcast.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ytk_mp4j_tpu import meta
from ytk_mp4j_tpu.comm import keycodec
from ytk_mp4j_tpu.comm import progress as progress_mod
from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.operands import Operand, Operands
from ytk_mp4j_tpu.operators import Operator, Operators
from ytk_mp4j_tpu.ops import collectives as coll
from ytk_mp4j_tpu.ops import ring as ring_ops
from ytk_mp4j_tpu.ops import ring_kernel
from ytk_mp4j_tpu.ops import sparse as sparse_ops
from ytk_mp4j_tpu.parallel.mesh import make_mesh, DEFAULT_AXIS
from ytk_mp4j_tpu.utils import trace


def _x64_enabled() -> bool:
    return bool(jax.config.jax_enable_x64)


_pow2_bucket = keycodec.pow2_bucket


class PendingMap:
    """Deferred result of :meth:`TpuCommCluster.allreduce_map_async`.

    The device collective and the device->host copy are already in
    flight when this handle exists; :meth:`result` performs the single
    blocking fetch, decodes, and mutates the call's maps in place
    (identical post-state to the synchronous ``allreduce_map``).
    Chaining k dispatches before resolving any handle overlaps the k
    host encodes with device work and d2h transfers — the synchronous
    API instead pays one full dispatch+fetch round trip per call. What
    the chaining buys on the present machine (a scalar round trip is
    under 1 ms) is not measured."""

    def __init__(self, codec, codes, ov, maps):
        self._codec = codec
        self._codes = codes
        self._ov = ov
        self._maps = maps
        self._done = False

    def result(self):
        """Block, decode, and mutate the maps in place; idempotent."""
        if not self._done:
            if self._codec is not None:
                merged = TpuCommCluster._decode_union(
                    self._codec, self._codes, self._ov)
                for m in self._maps:
                    m.clear()
                    m.update(merged)
                self._ov = None   # release the device buffer
            self._done = True
        return self._maps


class TpuCommCluster:
    """SPMD collectives over ``n`` devices of a mesh.

    Parameters
    ----------
    n: number of ranks (devices); defaults to all devices. Non-powers-of-2
       are supported (mesh over a device subset).
    mesh: use an existing 1-D mesh instead.
    """

    def __init__(self, n: int | None = None, mesh: Mesh | None = None,
                 axis_name: str = DEFAULT_AXIS):
        if mesh is None:
            mesh = make_mesh(n, axis_name)
        self.mesh = mesh
        if len(mesh.axis_names) == 1:
            # flat cluster: ranks along one axis
            self.axis_name = mesh.axis_names[0]
            self.n = mesh.shape[self.axis_name]
        else:
            # hierarchical cluster (e.g. inter x intra, the device-side
            # analogue of process x thread nesting): ranks are row-major
            # over all axes; collectives run over the axis tuple and XLA
            # stages them across DCN/ICI
            self.axis_name = tuple(mesh.axis_names)
            self.n = 1
            for a in mesh.axis_names:
                self.n *= mesh.shape[a]
        self._row_sharding = NamedSharding(mesh, P(self.axis_name))
        self._jits: dict = {}
        # persistent key<->code vocabularies for the map collectives
        # (grow-only, one per key kind — see comm.keycodec)
        self._codecs: dict[str, object] = {}

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    @property
    def slave_num(self) -> int:
        return self.n

    def _check_operand(self, operand: Operand):
        if not operand.is_numeric:
            raise Mp4jError(
                f"{operand.name} operands are host-only; use the socket / "
                "in-process backend (SURVEY.md section 7 phase 4)")
        if operand.dtype.itemsize == 8 and not _x64_enabled():
            raise Mp4jError(
                f"{operand.name} needs jax_enable_x64 (64-bit dtypes are "
                "not enabled on this backend)")

    def _check_root(self, root: int):
        if not (0 <= root < self.n):
            raise Mp4jError(f"root {root} out of range [0, {self.n})")

    def _norm_arrays(self, arrs, operand: Operand, lo: int, hi: int | None):
        if len(arrs) != self.n:
            raise Mp4jError(f"expected {self.n} per-rank arrays, got {len(arrs)}")
        for a in arrs:
            if not isinstance(a, np.ndarray):
                raise Mp4jError(
                    "per-rank buffers must be numpy arrays (results are "
                    f"written back in place); got {type(a).__name__}")
        out = [operand.check_array(a) for a in arrs]
        shape0 = out[0].shape
        for a in out:
            if a.shape != shape0:
                raise Mp4jError("per-rank arrays must share a shape")
        if hi is None:
            hi = shape0[0] if out[0].ndim == 1 else out[0].size
        if lo != 0 or hi != (shape0[0] if out[0].ndim == 1 else out[0].size):
            if out[0].ndim != 1:
                raise Mp4jError("[from, to) ranges require 1-D arrays")
        if not (0 <= lo <= hi <= (shape0[0] if out[0].ndim == 1 else out[0].size)):
            raise Mp4jError(f"range [{lo}, {hi}) out of bounds")
        return out, lo, hi

    def _stack(self, blocks: list[np.ndarray]):
        """Stack per-rank equal blocks into a device array sharded by rank."""
        stacked = np.stack(blocks, axis=0)
        return jax.device_put(stacked, self._row_sharding)

    # -- algorithm selection (reference parity: ProcessCommSlave's
    # algo="auto"/"tree"/"rhd"/"ring"). "xla": one fused XLA collective
    # (default — the compiler schedules ICI DMA). "ring":
    # hand-scheduled ppermute ring (ops.ring). "rdma": the Pallas RDMA
    # ring kernel (ops.ring_kernel) — the explicit-transport path;
    # interpreted on non-TPU meshes, compiled (barrier + credit
    # backpressure) on TPU. "auto" — the host backends' size-aware
    # default — is accepted for dispatch consistency and resolves to
    # "xla": on device the compiler already schedules per topology, so
    # the fused collective IS the auto choice.
    _ALGOS = ("auto", "xla", "ring", "rdma")

    def _check_algo(self, algo: str) -> str:
        if algo not in self._ALGOS:
            raise Mp4jError(f"algo must be one of {self._ALGOS}, "
                            f"got {algo!r}")
        if algo == "auto":
            return "xla"
        if algo != "xla" and isinstance(self.axis_name, tuple):
            raise Mp4jError(
                f"algo={algo!r} rings over a single ICI axis; "
                "hierarchical meshes use the default 'xla' path")
        return algo

    def _interpret_kernels(self) -> bool:
        """Pallas kernels compile only on TPU meshes; interpret them on
        the virtual CPU meshes the tests and the driver dry-run use."""
        return self.mesh.devices.flat[0].platform != "tpu"

    def _jit(self, key, build):
        fn = self._jits.get(key)
        if fn is None:
            fn = build()
            self._jits[key] = fn
        return fn

    # ------------------------------------------------------------------
    # dense collectives (reference: *Array methods, SURVEY.md section 2)
    # ------------------------------------------------------------------
    def allreduce_array(self, arrs, operand: Operand = Operands.FLOAT,
                        operator: Operator = Operators.SUM,
                        from_: int = 0, to: int | None = None,
                        algo: str = "xla"):
        """Element-wise reduce ``arr[from_:to]`` across ranks, in place.

        ``algo`` selects the schedule (see ``_ALGOS``): the fused XLA
        collective (default), the ppermute ring, or the Pallas RDMA
        ring kernel — all wire-identical in results."""
        self._check_operand(operand)
        algo = self._check_algo(algo)
        arrs, lo, hi = self._norm_arrays(arrs, operand, from_, to)
        if hi == lo:
            return arrs
        flat = [a[lo:hi] if a.ndim == 1 else a.reshape(-1) for a in arrs]
        L = flat[0].size

        def build():
            if algo == "xla":
                @partial(shard_map, mesh=self.mesh,
                         in_specs=P(self.axis_name),
                         out_specs=P(self.axis_name))
                def f(x):  # x: [1, L]
                    return coll.allreduce(x, operator, self.axis_name)
                return jax.jit(f)

            axis = self.axis_name
            n = self.n
            interpret = self._interpret_kernels()

            # the pallas interpreter / the ring's data-dependent chunk
            # walk defeat static replication inference; differential
            # tests cover algo equivalence
            @partial(shard_map, mesh=self.mesh, check_vma=False,
                     in_specs=P(axis), out_specs=P(axis))
            def f(x):  # x: [1, L]
                v = x[0]
                if algo == "rdma":
                    return ring_kernel.ring_allreduce_kernel(
                        v, operator, axis, interpret=interpret)[None]
                padL = meta.padded_block(L, n) * n
                if padL != L:
                    ident = jnp.asarray(operator.identity(v.dtype),
                                        dtype=v.dtype)
                    v = jnp.concatenate(
                        [v, jnp.full((padL - L,), ident, v.dtype)])
                return ring_ops.ring_allreduce(v, operator, axis)[:L][None]
            return jax.jit(f)

        fn = self._jit(("allreduce", L, operand.dtype, operator, algo),
                       build)
        res = np.asarray(fn(self._stack(flat)))
        for r, a in enumerate(arrs):
            if a.ndim == 1:
                a[lo:hi] = res[r]
            else:
                np.copyto(a, res[r].reshape(a.shape))
        return arrs

    def reduce_array(self, arrs, operand: Operand = Operands.FLOAT,
                     operator: Operator = Operators.SUM, root: int = 0,
                     from_: int = 0, to: int | None = None):
        """Reduce into ``root``'s array; other ranks' buffers unchanged."""
        self._check_operand(operand)
        self._check_root(root)
        arrs, lo, hi = self._norm_arrays(arrs, operand, from_, to)
        if hi == lo:
            return arrs
        flat = [a[lo:hi] if a.ndim == 1 else a.reshape(-1) for a in arrs]
        L = flat[0].size

        def build():
            @partial(shard_map, mesh=self.mesh,
                     in_specs=P(self.axis_name), out_specs=P(self.axis_name))
            def f(x):
                return coll.reduce(x, operator, root, self.axis_name)
            return jax.jit(f)

        fn = self._jit(("reduce", L, operand.dtype, operator), build)
        res = np.asarray(fn(self._stack(flat)))
        a = arrs[root]
        if a.ndim == 1:
            a[lo:hi] = res[root]
        else:
            np.copyto(a, res[root].reshape(a.shape))
        return arrs

    def broadcast_array(self, arrs, operand: Operand = Operands.FLOAT,
                        root: int = 0, from_: int = 0, to: int | None = None):
        """Copy ``root``'s ``arr[from_:to]`` into every rank's array."""
        self._check_operand(operand)
        self._check_root(root)
        arrs, lo, hi = self._norm_arrays(arrs, operand, from_, to)
        if hi == lo:
            return arrs
        flat = [a[lo:hi] if a.ndim == 1 else a.reshape(-1) for a in arrs]
        L = flat[0].size

        def build():
            @partial(shard_map, mesh=self.mesh,
                     in_specs=P(self.axis_name), out_specs=P(self.axis_name))
            def f(x):
                return coll.broadcast(x, root, self.axis_name)
            return jax.jit(f)

        fn = self._jit(("broadcast", L, operand.dtype, root), build)
        res = np.asarray(fn(self._stack(flat)))
        for r, a in enumerate(arrs):
            if a.ndim == 1:
                a[lo:hi] = res[r]
            else:
                np.copyto(a, res[r].reshape(a.shape))
        return arrs

    # -- segment-based family. ``ranges`` gives each rank's owned segment
    # of a common full-length array (reference: per-rank from/to counts in
    # ArrayMetaData, SURVEY.md section 2). Default: block partition of the
    # whole array via meta.partition_range.
    def _norm_ranges(self, arrs, ranges):
        L = arrs[0].shape[0]
        if ranges is None:
            ranges = meta.partition_range(0, L, self.n)
        if len(ranges) != self.n:
            raise Mp4jError(f"need {self.n} ranges, got {len(ranges)}")
        prev = None
        for (s, e) in ranges:
            if not (0 <= s <= e <= L):
                raise Mp4jError(f"range ({s}, {e}) out of bounds for {L}")
            if prev is not None and s != prev:
                raise Mp4jError("ranges must be contiguous in rank order")
            prev = e
        return ranges

    @staticmethod
    def _max_block(ranges) -> int:
        return max(1, max(e - s for s, e in ranges))

    def _run_segment_gather(self, arrs, operand: Operand, ranges,
                            algo: str = "xla"):
        """Shared core of (all)gather: pad each rank's segment to the max
        block, all_gather on device, return the [n, B] result."""
        if arrs[0].ndim != 1:
            raise Mp4jError("segment collectives require 1-D arrays")
        algo = self._check_algo(algo)
        ranges = self._norm_ranges(arrs, ranges)
        B = self._max_block(ranges)
        if algo == "rdma":
            B = ring_kernel.round_up_chunk(B, operand.dtype,
                                           self._interpret_kernels())
        blocks = []
        for r, (s, e) in enumerate(ranges):
            b = np.zeros(B, dtype=operand.dtype)
            b[: e - s] = arrs[r][s:e]
            blocks.append(b)

        def build():
            if algo == "xla":
                @partial(shard_map, mesh=self.mesh, check_vma=False,
                         in_specs=P(self.axis_name),
                         out_specs=P(None, None))
                def f(x):  # x: [1, B] -> [n, B] replicated
                    return coll.allgather(x, self.axis_name, tiled=True)
                return jax.jit(f)

            axis = self.axis_name
            n = self.n
            interpret = self._interpret_kernels()

            @partial(shard_map, mesh=self.mesh, check_vma=False,
                     in_specs=P(axis), out_specs=P(None, None))
            def f(x):  # x: [1, B] -> [n, B] replicated
                if algo == "rdma":
                    y = ring_kernel.ring_allgather_kernel(
                        x[0], axis, interpret=interpret)
                else:
                    y = ring_ops.ring_allgather(x[0], axis)
                return y.reshape(n, B)
            return jax.jit(f)

        fn = self._jit(("allgather", B, operand.dtype, algo), build)
        return np.asarray(fn(self._stack(blocks))), ranges

    def allgather_array(self, arrs, operand: Operand = Operands.FLOAT,
                        ranges=None, algo: str = "xla"):
        """Each rank owns ``arr[ranges[rank]]``; afterwards every rank's
        array holds all segments. ``algo`` selects the schedule (see
        ``_ALGOS``)."""
        self._check_operand(operand)
        arrs, _, _ = self._norm_arrays(arrs, operand, 0, None)
        res, ranges = self._run_segment_gather(arrs, operand, ranges, algo)
        for a in arrs:
            for r, (s, e) in enumerate(ranges):
                a[s:e] = res[r, : e - s]
        return arrs

    def gather_array(self, arrs, operand: Operand = Operands.FLOAT,
                     root: int = 0, ranges=None):
        """Root's array receives every rank's segment; others unchanged."""
        self._check_operand(operand)
        self._check_root(root)
        arrs, _, _ = self._norm_arrays(arrs, operand, 0, None)
        res, ranges = self._run_segment_gather(arrs, operand, ranges)
        a = arrs[root]
        for r, (s, e) in enumerate(ranges):
            a[s:e] = res[r, : e - s]
        return arrs

    def scatter_array(self, arrs, operand: Operand = Operands.FLOAT,
                      root: int = 0, ranges=None):
        """Rank r receives segment ``ranges[r]`` of ``root``'s array."""
        self._check_operand(operand)
        self._check_root(root)
        arrs, _, _ = self._norm_arrays(arrs, operand, 0, None)
        if arrs[0].ndim != 1:
            raise Mp4jError("segment collectives require 1-D arrays")
        ranges = self._norm_ranges(arrs, ranges)
        # In the single-controller runtime every rank's buffer lives in
        # host memory, so scatter is a pure host copy of root's segments —
        # a device round-trip would move the same bytes twice for zero
        # effect. (The SPMD functional layer has a true in-jit scatter for
        # multi-host use inside jitted programs.)
        src = arrs[root]
        for r, (s, e) in enumerate(ranges):
            if r != root:
                arrs[r][s:e] = src[s:e]
        return arrs

    def reduce_scatter_array(self, arrs, operand: Operand = Operands.FLOAT,
                             operator: Operator = Operators.SUM, ranges=None,
                             algo: str = "xla"):
        """Every rank contributes its full array; rank r ends with segment
        ``ranges[r]`` of the element-wise reduction (other positions
        unchanged). ``algo`` selects the schedule (see ``_ALGOS``)."""
        self._check_operand(operand)
        algo = self._check_algo(algo)
        arrs, _, _ = self._norm_arrays(arrs, operand, 0, None)
        if arrs[0].ndim != 1:
            raise Mp4jError("segment collectives require 1-D arrays")
        ranges = self._norm_ranges(arrs, ranges)
        lo, hi = ranges[0][0], ranges[-1][1]
        B = meta.padded_block(hi - lo, self.n)
        if algo == "rdma":
            B = ring_kernel.round_up_chunk(B, operand.dtype,
                                           self._interpret_kernels())
        pad = self.n * B
        ident = operator.identity(operand.dtype)
        blocks = []
        for r in range(self.n):
            b = np.full(pad, ident, dtype=operand.dtype)
            b[: hi - lo] = arrs[r][lo:hi]
            blocks.append(b)

        def build():
            if algo == "xla":
                @partial(shard_map, mesh=self.mesh,
                         in_specs=P(self.axis_name),
                         out_specs=P(self.axis_name))
                def f(x):  # x: [1, n*B]
                    y = coll.reduce_scatter(x[0], operator, self.axis_name)
                    return y[None]  # [1, B]
                return jax.jit(f)

            axis = self.axis_name
            n = self.n
            interpret = self._interpret_kernels()

            @partial(shard_map, mesh=self.mesh, check_vma=False,
                     in_specs=P(axis), out_specs=P(axis))
            def f(x):  # x: [1, n*B]
                if algo == "rdma":
                    y = ring_kernel.ring_reduce_scatter_kernel(
                        x[0], operator, axis, interpret=interpret)
                else:
                    # the ppermute ring leaves member r with chunk
                    # (r+1)%n; one further hop right restores the
                    # block-r-to-rank-r layout of the XLA path
                    y = ring_ops.ring_reduce_scatter(x[0], operator, axis)
                    y = lax.ppermute(y, axis,
                                     [(i, (i + 1) % n) for i in range(n)])
                return y[None]  # [1, B]
            return jax.jit(f)

        fn = self._jit(("reduce_scatter", pad, operand.dtype, operator,
                        algo), build)
        res = np.asarray(fn(self._stack(blocks)))  # [n, B]
        # Padded-block layout: device block r covers [lo + r*B, lo + (r+1)*B).
        # Write each rank's owned (uneven) range from the covering blocks.
        full = res.reshape(-1)[: hi - lo]
        for r, (s, e) in enumerate(ranges):
            arrs[r][s:e] = full[s - lo: e - lo]
        return arrs


    # ------------------------------------------------------------------
    # sparse map collectives (reference: *Map methods, SURVEY.md 3c)
    #
    # Keys live on the host (strings are not TPU-representable — the
    # reference likewise keeps them in Kryo land); values ride the device
    # as packed (code, value) buffers through ops.sparse. In-place
    # semantics: each rank's dict is mutated like the reference's maps.
    # ------------------------------------------------------------------
    def _norm_maps(self, maps, operand: Operand):
        if len(maps) != self.n:
            raise Mp4jError(f"expected {self.n} per-rank maps, got {len(maps)}")
        for m in maps:
            if not isinstance(m, dict):
                raise Mp4jError(
                    f"per-rank operands must be dicts, got {type(m).__name__}")
        self._check_operand(operand)
        return maps

    def _encode_maps(self, maps, operand: Operand, operator: Operator):
        """Pack each rank's entries into SENTINEL-padded (code, value)
        buffers of equal static length via the cluster's PERSISTENT key
        codec (``comm.keycodec``) — no per-call union sort, no per-entry
        Python loop. Returns ``(codec, idx, val, vshape, cap)`` with
        ``cap`` an upper bound on the union's unique-code count, or
        ``None`` when every map is empty.

        Round-2 history: this used to re-derive
        ``sorted(set().union(*maps))`` and pack entry-by-entry on every
        call, which made the device path LOSE to the socket dict loop at
        configs[2]; a sparse-gradient stream's
        vocabulary is near-persistent, so key->code translation is now
        amortized across calls."""
        total = sum(len(m) for m in maps)
        if total == 0:
            return None
        for m in maps:
            if m:
                k0 = next(iter(m))
                vshape = np.shape(m[k0])
                break
        kind = keycodec.kind_of(k0)
        codec = self._codecs.get(kind)
        if codec is None:
            codec = self._codecs[kind] = keycodec.codec_for_kind(kind)
        # round the per-rank slot count up to a power of 2: real sparse
        # gradient streams drift in key count every step, and an exact
        # Lmax would join the jit key and recompile per step; padding is
        # SENTINEL/identity so the bucket rounding is semantically free
        # and bounds the compile count at O(log max-keys) programs
        Lmax = _pow2_bucket(max(len(m) for m in maps))
        ident = operator.identity(operand.dtype)
        idx = np.full((self.n, Lmax), sparse_ops.SENTINEL, dtype=np.int32)
        val = np.full((self.n, Lmax) + vshape, ident, dtype=operand.dtype)
        for r, m in enumerate(maps):
            c = len(m)
            if c == 0:
                continue
            idx[r, :c] = codec.encode(m.keys(), c)
            val[r, :c] = keycodec.pack_values(m.values(), c, vshape,
                                              operand.dtype)
        # every key of this call is in the vocabulary, so the union's
        # unique-code count is bounded by both the vocabulary size and
        # the total entry count
        return codec, idx, val, vshape, min(codec.size, total)

    @staticmethod
    def _decode_union(codec, codes, ov):
        """Host-known union codes + the device's value buffer -> one
        merged dict (bulk zip; map values are shared across ranks, as
        the round-2 decode's single ``merged`` dict already did).
        ``ov`` is a DEVICE array; the asarray here is the call's single
        round-trip."""
        vals = np.asarray(ov)[: codes.size]
        return dict(zip(codec.decode(codes), list(vals)))

    def _device_sparse_allreduce(self, idx, val, capacity, operator):
        # same bucket rounding as _encode_maps, for the union capacity:
        # the output is SENTINEL-padded past the true union, so callers
        # (which skip SENTINEL slots) see no semantic difference
        capacity = _pow2_bucket(capacity)
        Lmax = idx.shape[1]
        vshape = val.shape[2:]

        def build():
            @partial(shard_map, mesh=self.mesh, check_vma=False,
                     in_specs=(P(self.axis_name), P(self.axis_name)),
                     out_specs=(P(None), P(None)))
            def f(i, v):  # [1, L] / [1, L, *vshape] per shard
                return sparse_ops.sparse_allreduce(
                    i[0], v[0], capacity, operator, self.axis_name)
            return jax.jit(f)

        key = ("sparse_allreduce", Lmax, capacity, vshape,
               val.dtype.str, operator)
        fn = self._jit(key, build)
        # DEVICE arrays out: callers fetch only what they need — every
        # np.asarray is a blocking device->host copy, and the map
        # family never fetches oi at all (see _union_codes)
        return fn(jax.device_put(idx, self._row_sharding),
                  jax.device_put(val, self._row_sharding))

    @staticmethod
    def _union_codes(idx: np.ndarray) -> np.ndarray:
        """The union's code list, host-side: ``segment_reduce_sorted``
        packs unique codes ascending with SENTINEL padding at the end —
        exactly ``np.unique`` of the staged buffers minus the sentinel.
        Computing it here makes the device's ``oi`` output redundant, so
        the map collectives pay ONE device fetch per call (ov), not two
        sequential ones."""
        codes = np.unique(idx)
        if codes.size and codes[-1] == sparse_ops.SENTINEL:
            codes = codes[:-1]
        return codes

    def allreduce_map(self, maps, operand: Operand = Operands.DOUBLE,
                      operator: Operator = Operators.SUM):
        """Key-union reduce: every rank's dict becomes the union of all
        keys with shared keys reduced by ``operator``."""
        maps = self._norm_maps(maps, operand)
        enc = self._encode_maps(maps, operand, operator)
        if enc is None:
            return maps
        codec, idx, val, _vshape, cap = enc
        _oi, ov = self._device_sparse_allreduce(idx, val, cap, operator)
        merged = self._decode_union(codec, self._union_codes(idx), ov)
        for m in maps:
            m.clear()
            m.update(merged)
        return maps

    def allreduce_map_async(self, maps,
                            operand: Operand = Operands.DOUBLE,
                            operator: Operator = Operators.SUM
                            ) -> PendingMap:
        """Pipelined :meth:`allreduce_map`: dispatch the device
        collective and start the device->host value copy, but defer the
        blocking fetch/decode/mutation to the returned handle's
        ``result()``. Per-call work overlaps across chained dispatches,
        so a k-deep chain pays ~one round trip, not k (measured on
        the previous installation, 2026-07, where a round trip was
        ~100 ms; not on the present machine). The input dicts must
        not be mutated between dispatch and ``result()``."""
        maps = self._norm_maps(maps, operand)
        enc = self._encode_maps(maps, operand, operator)
        if enc is None:
            return PendingMap(None, None, None, maps)
        codec, idx, val, _vshape, cap = enc
        _oi, ov = self._device_sparse_allreduce(idx, val, cap, operator)
        try:
            ov.copy_to_host_async()
        except (AttributeError, RuntimeError):  # pragma: no cover
            pass    # prefetch is best-effort; result() fetches anyway
        return PendingMap(codec, self._union_codes(idx), ov, maps)

    def reduce_map(self, maps, operand: Operand = Operands.DOUBLE,
                   operator: Operator = Operators.SUM, root: int = 0):
        """Key-union reduce into ``root``'s dict; others unchanged."""
        self._check_root(root)
        maps = self._norm_maps(maps, operand)
        enc = self._encode_maps(maps, operand, operator)
        if enc is None:
            return maps
        codec, idx, val, _vshape, cap = enc
        _oi, ov = self._device_sparse_allreduce(idx, val, cap, operator)
        merged = self._decode_union(codec, self._union_codes(idx), ov)
        maps[root].clear()
        maps[root].update(merged)
        return maps

    def reduce_scatter_map(self, maps, operand: Operand = Operands.DOUBLE,
                           operator: Operator = Operators.SUM):
        """Key-union reduce, then each rank keeps the keys hashing to it
        (meta.key_partition — identical placement on both backends; the
        codec caches the blake2b placement per key, which dominates the
        per-entry cost otherwise)."""
        maps = self._norm_maps(maps, operand)
        enc = self._encode_maps(maps, operand, operator)
        if enc is None:
            return maps
        codec, idx, val, _vshape, cap = enc
        _oi, ov = self._device_sparse_allreduce(idx, val, cap, operator)
        codes = self._union_codes(idx)
        vals = np.asarray(ov)[: codes.size]   # the single device fetch
        parts = codec.partition(codes, self.n)
        for r, m in enumerate(maps):
            mine = parts == r
            m.clear()
            m.update(zip(codec.decode(codes[mine]), list(vals[mine])))
        return maps

    def allgather_map(self, maps, operand: Operand = Operands.DOUBLE):
        """Disjoint union: every rank's dict becomes the union of all
        ranks' entries. Duplicate keys raise (ambiguous without an
        operator). Composition of gather + broadcast, like the socket
        backend."""
        self.gather_map(maps, operand, root=0)
        return self.broadcast_map(maps, operand, root=0)

    def gather_map(self, maps, operand: Operand = Operands.DOUBLE,
                   root: int = 0):
        """Disjoint union into ``root``'s dict; others unchanged. A
        duplicate key raises naming the key and both owner ranks
        (contract parity with the socket backend)."""
        self._check_root(root)
        maps = self._norm_maps(maps, operand)
        total = sum(len(m) for m in maps)
        union: dict = {}
        for m in maps:
            union.update(m)
        if len(union) != total:
            seen: dict = {}
            for r, m in enumerate(maps):
                for k in m:
                    if k in seen:
                        raise Mp4jError(
                            f"gather_map: duplicate key {k!r} owned by "
                            f"ranks {seen[k]} and {r}; use reduce_map "
                            f"to combine")
                    seen[k] = r
        maps[root].clear()
        maps[root].update(union)
        return maps

    def broadcast_map(self, maps, operand: Operand = Operands.DOUBLE,
                      root: int = 0):
        """Every rank's dict becomes a copy of ``root``'s."""
        self._check_root(root)
        maps = self._norm_maps(maps, operand)
        src = dict(maps[root])
        for r, m in enumerate(maps):
            if r != root:
                m.clear()
                m.update(src)
        return maps

    def scatter_map(self, maps, operand: Operand = Operands.DOUBLE,
                    root: int = 0, partitioner=None):
        """Rank r receives the subset of ``root``'s entries whose keys
        hash to r (meta.key_partition).

        ``partitioner(key) -> rank`` overrides the placement rule —
        contract parity with ``ProcessCommSlave.scatter_map`` (the
        thread backend's global-thread-rank placement relies on it)."""
        self._check_root(root)
        maps = self._norm_maps(maps, operand)
        if partitioner is None:
            partitioner = lambda k: meta.key_partition(k, self.n)  # noqa: E731
        src = dict(maps[root])
        shares: list[dict] = [{} for _ in range(self.n)]
        for k, v in src.items():
            shares[meta.check_partition_rank(partitioner(k), self.n,
                                             k)][k] = v
        for r, m in enumerate(maps):
            m.clear()
            m.update(shares[r])
        return maps

    def reset_map_vocabularies(self) -> None:
        """Drop the persistent key<->code vocabularies (and their cached
        partitions). The codecs are grow-only; on a long-lived cluster
        whose key space CHURNS (rather than stabilizes) they — and the
        union capacity buckets keyed on them — grow without bound.
        After a reset the next map collective rebuilds from the live
        keys. Compiled programs are kept (they are keyed on shapes, not
        vocabularies)."""
        self._codecs.clear()

    # ------------------------------------------------------------------
    # nonblocking collectives (ISSUE 11): the device path is a single-
    # controller SPMD driver whose dispatches are ALREADY asynchronous
    # under JAX's lazy execution — the dense i* twins execute eagerly
    # (the launch returns before the device finishes; materialization
    # blocks, exactly as for the blocking API) and return resolved
    # futures, while iallreduce_map rides the existing chained-
    # dispatch machinery (PendingMap) behind a lazily-resolving future
    # so k chained maps pay ~one device round trip, not k.
    # ------------------------------------------------------------------
    def iallreduce(self, arrs, operand: Operand = Operands.FLOAT,
                   operator: Operator = Operators.SUM,
                   from_: int = 0, to: int | None = None,
                   algo: str = "auto"):
        """Eager nonblocking :meth:`allreduce_array` (resolved
        future)."""
        return progress_mod.eager_future(
            self, "allreduce_array", arrs, operand, operator,
            from_=from_, to=to, algo=algo)

    def ireduce_scatter(self, arrs, operand: Operand = Operands.FLOAT,
                        operator: Operator = Operators.SUM,
                        ranges=None):
        """Eager nonblocking :meth:`reduce_scatter_array`."""
        return progress_mod.eager_future(
            self, "reduce_scatter_array", arrs, operand, operator,
            ranges=ranges)

    def iallgather(self, arrs, operand: Operand = Operands.FLOAT,
                   ranges=None):
        """Eager nonblocking :meth:`allgather_array`."""
        return progress_mod.eager_future(
            self, "allgather_array", arrs, operand, ranges=ranges)

    def igather(self, arrs, operand: Operand = Operands.FLOAT,
                root: int = 0, ranges=None):
        """Eager nonblocking :meth:`gather_array`."""
        return progress_mod.eager_future(
            self, "gather_array", arrs, operand, root=root,
            ranges=ranges)

    def iallreduce_map(self, maps, operand: Operand = Operands.DOUBLE,
                       operator: Operator = Operators.SUM):
        """Nonblocking :meth:`allreduce_map` riding
        :meth:`allreduce_map_async`: the device collective and the
        d2h copy are in flight when this returns; ``wait()`` performs
        the single blocking fetch + decode (identical post-state to
        the blocking twin)."""
        pending = self.allreduce_map_async(maps, operand, operator)
        return progress_mod.DeferredFuture("allreduce_map",
                                           pending.result)

    def wait_all(self, timeout: float | None = None) -> None:
        """Collective-boundary drain: the dense device path is eager
        and ``iallreduce_map`` futures resolve at ``wait()`` — no
        scheduler state to drain; kept for portable code."""

    # ------------------------------------------------------------------
    def barrier(self):
        """Synchronize: run a trivial device collective to completion."""
        def build():
            @partial(shard_map, mesh=self.mesh, in_specs=P(self.axis_name),
                     out_specs=P(self.axis_name))
            def f(x):
                return x + coll.barrier(self.axis_name)
            return jax.jit(f)
        fn = self._jit(("barrier",), build)
        tok = jax.device_put(np.zeros((self.n, 1), np.int32),
                             self._row_sharding)
        np.asarray(fn(tok))


# per-collective tracing (utils.trace; zero overhead when disabled)
trace.instrument(TpuCommCluster)
