"""Multi-host backend — the DCN-scale rendezvous and host-level slave.

In the reference, scaling past one machine means pointing every slave
JVM at the master's host:port (SURVEY.md section 3a). The TPU-native
analogue of that rendezvous is ``jax.distributed.initialize``: the
coordinator assigns process indices (ranks) and wires up the PJRT
distributed runtime, after which XLA collectives ride ICI within a slice
and DCN across hosts.

Two layers are exposed here:

- :func:`init_distributed` + :class:`DistributedComm` — a host-level
  slave mirroring the ``ProcessCommSlave`` API (rank / slave_num /
  barrier / info / close + the 7 collectives x {array, map}) where each
  RANK IS A PROCESS (host). Dense reduce/allreduce/reduce-scatter with
  the built-in SUM/MAX/MIN ride ONE device collective (psum / pmax /
  pmin / psum_scatter over a one-device-per-process mesh — 2L(n-1)/n
  wire bytes); PROD, custom operators, and the gather family use
  ``multihost_utils`` allgather. Numeric map operands ride the device
  plane too (round 4): key<->code vocabularies are kept identical on
  every process — only NOVEL keys ride a small pickled exchange, near
  empty once a gradient stream's vocabulary stabilizes — and the
  values travel as one device sparse allreduce; object values (and
  64-bit without x64) fall back to the pickled whole-map exchange
  (the Kryo analogue at DCN scale).
- :func:`global_mesh` / :func:`hier_global_mesh` — mesh builders over
  ALL processes' devices for the perf path: user jit code with
  ``shard_map`` + ``ops.collectives`` (and the model families) runs
  unchanged on a global mesh; XLA stages psum across ICI then DCN
  exactly like the reference's thread-then-process nesting (SURVEY.md
  section 3d).

Single-process fallback: constructing :class:`DistributedComm` without
``jax.distributed`` initialized yields a 1-rank comm (useful for code
that runs unmodified on one host or many).
"""

from __future__ import annotations

import pickle

import numpy as np

import jax
from jax.experimental import multihost_utils
from jax.sharding import Mesh

from ytk_mp4j_tpu import meta
from ytk_mp4j_tpu.comm.context import CommSlave
from ytk_mp4j_tpu.comm import progress as progress_mod
from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.operands import Operand, Operands
from ytk_mp4j_tpu.operators import Operator, Operators
from ytk_mp4j_tpu.parallel.mesh import DEFAULT_AXIS, INTER_AXIS, INTRA_AXIS
from ytk_mp4j_tpu.utils import trace


def init_distributed(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None,
                     **kwargs) -> "DistributedComm":
    """Join the distributed job and return the host-level comm.

    Mirrors the reference's slave constructor (master host:port ->
    coordinator address; expected slave count -> num_processes; SURVEY.md
    section 3a). With no arguments, JAX auto-detects cluster settings
    (TPU pod metadata) or falls back to single-process.
    """
    if coordinator_address is not None or num_processes is not None:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id, **kwargs)
    return DistributedComm()


def global_mesh(axis_name: str = DEFAULT_AXIS) -> Mesh:
    """1-D mesh over every device of every process (the flat perf path)."""
    return Mesh(np.asarray(jax.devices()), (axis_name,))


def hier_global_mesh(axis_names: tuple[str, str] = (INTER_AXIS, INTRA_AXIS),
                     ) -> Mesh:
    """2-D (process x local-device) mesh: ``inter`` crosses hosts (DCN),
    ``intra`` stays on-host/slice (ICI) — the device-side analogue of the
    reference's process x thread nesting (SURVEY.md section 3d)."""
    P = jax.process_count()
    L = jax.local_device_count()
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    return Mesh(np.asarray(devs).reshape(P, L), axis_names)


class DistributedComm(CommSlave):
    """Host-level slave over the JAX distributed runtime.

    One rank per PROCESS. Collectives move host numpy data through the
    devices (``multihost_utils``), with in-place buffer semantics
    matching the other backends. Use the mesh builders above + the
    functional layer for device-resident perf-path work.
    """

    def __init__(self):
        self._rank = jax.process_index()
        self._n = jax.process_count()
        self._closed = False
        self.final_code: int | None = None  # set by close()
        self._pmesh: Mesh | None = None
        self._djits: dict = {}
        # key kind -> codec, kept IDENTICAL across processes (grown
        # only inside _union_device's synchronized novel-key exchange)
        self._codecs_by_kind: dict[str, object] = {}
        # job-wide AND of jax_enable_x64 (see _job_x64)
        self._x64_all: bool | None = None

    # -- identity / control plane --------------------------------------
    @property
    def rank(self) -> int:
        return self._rank

    @property
    def slave_num(self) -> int:
        return self._n

    def barrier(self, name: str | None = None) -> None:
        self._assert_open()
        tag = name if name is not None else "mp4j_barrier"
        multihost_utils.sync_global_devices(tag)

    def close(self, code: int = 0) -> None:
        """Exchange exit codes, synchronize, then leave the job.

        Matches the reference's close(code) aggregation: every process
        learns the job-wide worst code before teardown —
        :attr:`final_code` is ``max`` over all ranks' codes (the
        coordinator-side ``Master.final_code`` equivalent), and a
        nonzero aggregate is logged on every rank."""
        if self._closed:
            return
        if self._n > 1:
            codes = self._exchange_obj(int(code))
            self.final_code = max(codes)
            if self.final_code != 0:
                self.error(f"job closing with aggregate exit code "
                           f"{self.final_code} (per-rank: {codes})")
            multihost_utils.sync_global_devices("mp4j_close")
            jax.distributed.shutdown()
        else:
            self.final_code = int(code)
        self._closed = True

    def _assert_open(self):
        if self._closed:
            raise Mp4jError("comm is closed")

    # -- internals ------------------------------------------------------
    def _check_numeric(self, operand: Operand):
        if not operand.is_numeric:
            raise Mp4jError(
                f"{operand.name} operands travel the map/object path on "
                "the distributed backend")
        if operand.dtype.itemsize == 8 and not jax.config.jax_enable_x64:
            raise Mp4jError(
                f"{operand.name} needs jax_enable_x64: the payload "
                "round-trips through the devices and would be silently "
                "downcast")

    def _norm_range(self, arr, operand: Operand, lo: int, hi: int | None):
        self._check_numeric(operand)
        arr = operand.check_array(arr)
        if arr.ndim != 1:
            raise Mp4jError("distributed path supports 1-D arrays")
        if hi is None:
            hi = len(arr)
        if not (0 <= lo <= hi <= len(arr)):
            raise Mp4jError(f"range [{lo}, {hi}) out of bounds")
        return arr, lo, hi

    def _allgather_rows(self, row: np.ndarray) -> np.ndarray:
        """[L] per process -> [P, L] on every process (device allgather)."""
        return np.asarray(multihost_utils.process_allgather(row))

    def _exchange_obj(self, obj) -> list:
        """Every process contributes one picklable object; returns the
        list of all processes' objects (rank-ordered). Pickled bytes ride
        a padded uint8 device allgather — the DCN Kryo analogue."""
        payload = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        n = np.asarray([payload.size], np.int64)
        sizes = self._allgather_rows(n)[:, 0]
        cap = int(sizes.max())
        buf = np.zeros(cap, np.uint8)
        buf[: payload.size] = payload
        rows = self._allgather_rows(buf)
        return [pickle.loads(rows[p, : sizes[p]].tobytes())
                for p in range(self._n)]

    def _bcast(self, arr: np.ndarray, root: int) -> np.ndarray:
        return np.asarray(multihost_utils.broadcast_one_to_all(
            arr, is_source=self._rank == root))

    def _check_root(self, root: int):
        if not (0 <= root < self._n):
            raise Mp4jError(f"root {root} out of range [0, {self._n})")

    @staticmethod
    def _reduce_rows(rows: np.ndarray, operator: Operator) -> np.ndarray:
        acc = rows[0].copy()
        for p in range(1, rows.shape[0]):
            acc = operator.np_fn(acc, rows[p])
        return acc

    # -- device data plane ---------------------------------------------
    # One device collective (psum / pmax / pmin / psum_scatter) over a
    # one-device-per-process mesh replaces allgather + host loop for the
    # built-in operators: n*L wire bytes become the collective's
    # 2L(n-1)/n. PROD and custom operators keep the allgather path —
    # XLA has no pprod/custom all-reduce primitive, and a log/exp
    # rewrite would change float semantics.
    # Gated on the builtin Operator OBJECTS (identity, not name): a
    # custom operator named "MAX" must keep the host-reduce path — its
    # fn is the semantics, pmax is not (same shadowing class as
    # sparse._SEGMENT_REDUCERS / _map_device_ok). The lax primitive
    # comes from operator.lax_collective, never from a name table.

    @staticmethod
    def _device_reduce_ok(operator: Operator) -> bool:
        """SUM / MAX / MIN ride one device collective; everything else
        keeps the allgather + host-reduce path, its only path."""
        return any(operator is b for b in
                   (Operators.SUM, Operators.MAX, Operators.MIN))

    def _proc_mesh(self) -> Mesh:
        if self._pmesh is None:
            per_proc: dict[int, object] = {}
            for d in sorted(jax.devices(),
                            key=lambda d: (d.process_index, d.id)):
                per_proc.setdefault(d.process_index, d)
            self._pmesh = Mesh(
                np.asarray([per_proc[p] for p in range(self._n)]),
                ("proc",))
        return self._pmesh

    def _device_rows_collective(self, kind: str, block: np.ndarray,
                                lax_name: str) -> np.ndarray:
        """Run ONE device collective over per-process [L] blocks.
        kind="allreduce" returns the reduced [L]; kind="reduce_scatter"
        expects [n*B] (n equal blocks) and returns this rank's [B].
        ``lax_name`` is the lax primitive (psum/pmax/pmin), taken from
        the builtin operator's ``lax_collective`` by the callers."""
        from functools import partial
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._proc_mesh()
        sharding = NamedSharding(mesh, P("proc"))
        key = (kind, lax_name, block.dtype.str, block.size)
        fn = self._djits.get(key)
        if fn is None:
            if kind == "allreduce":
                red = getattr(lax, lax_name)

                def body(x):
                    return red(x[0], "proc")[None]
            else:
                def body(x):
                    return lax.psum_scatter(
                        x[0].reshape(self._n, -1), "proc")[None]
            # the psum output is replicated but rides back under the
            # row sharding (each rank reads its own copy) — same
            # check_vma waiver as the driver backend
            fn = jax.jit(partial(
                jax.shard_map, mesh=mesh, check_vma=False,
                in_specs=P("proc"), out_specs=P("proc"))(body))
            self._djits[key] = fn
        garr = jax.make_array_from_process_local_data(
            sharding, block[None, :], (self._n, block.size))
        return np.asarray(fn(garr).addressable_data(0))[0]

    # -- dense-array collectives ---------------------------------------
    def allreduce_array(self, arr, operand: Operand = Operands.FLOAT,
                        operator: Operator = Operators.SUM,
                        from_: int = 0, to: int | None = None):
        self._assert_open()
        arr, lo, hi = self._norm_range(arr, operand, from_, to)
        if self._n == 1 or hi == lo:
            return arr
        if self._device_reduce_ok(operator):
            arr[lo:hi] = self._device_rows_collective(
                "allreduce", np.ascontiguousarray(arr[lo:hi]),
                operator.lax_collective)
            return arr
        rows = self._allgather_rows(np.ascontiguousarray(arr[lo:hi]))
        arr[lo:hi] = self._reduce_rows(rows, operator)
        return arr

    def reduce_array(self, arr, operand: Operand = Operands.FLOAT,
                     operator: Operator = Operators.SUM, root: int = 0,
                     from_: int = 0, to: int | None = None):
        self._assert_open()
        self._check_root(root)
        arr, lo, hi = self._norm_range(arr, operand, from_, to)
        if self._n == 1 or hi == lo:
            return arr
        if self._device_reduce_ok(operator):
            merged = self._device_rows_collective(
                "allreduce", np.ascontiguousarray(arr[lo:hi]),
                operator.lax_collective)
            if self._rank == root:
                arr[lo:hi] = merged
            return arr
        rows = self._allgather_rows(np.ascontiguousarray(arr[lo:hi]))
        if self._rank == root:
            arr[lo:hi] = self._reduce_rows(rows, operator)
        return arr

    def broadcast_array(self, arr, operand: Operand = Operands.FLOAT,
                        root: int = 0, from_: int = 0,
                        to: int | None = None):
        self._assert_open()
        self._check_root(root)
        arr, lo, hi = self._norm_range(arr, operand, from_, to)
        if self._n == 1 or hi == lo:
            return arr
        arr[lo:hi] = self._bcast(np.ascontiguousarray(arr[lo:hi]), root)
        return arr

    def _norm_ranges(self, arr, ranges):
        if ranges is None:
            ranges = meta.partition_range(0, len(arr), self._n)
        if len(ranges) != self._n:
            raise Mp4jError(f"need {self._n} ranges, got {len(ranges)}")
        return ranges

    def allgather_array(self, arr, operand: Operand = Operands.FLOAT,
                        ranges=None):
        self._assert_open()
        arr, _, _ = self._norm_range(arr, operand, 0, None)
        ranges = self._norm_ranges(arr, ranges)
        if self._n == 1:
            return arr
        B = max(1, max(e - s for s, e in ranges))
        block = np.zeros(B, dtype=operand.dtype)
        s, e = ranges[self._rank]
        block[: e - s] = arr[s:e]
        rows = self._allgather_rows(block)
        for p, (ps, pe) in enumerate(ranges):
            arr[ps:pe] = rows[p, : pe - ps]
        return arr

    def gather_array(self, arr, operand: Operand = Operands.FLOAT,
                     root: int = 0, ranges=None):
        self._assert_open()
        self._check_root(root)
        arr, _, _ = self._norm_range(arr, operand, 0, None)
        ranges = self._norm_ranges(arr, ranges)
        if self._n == 1:
            return arr
        B = max(1, max(e - s for s, e in ranges))
        block = np.zeros(B, dtype=operand.dtype)
        s, e = ranges[self._rank]
        block[: e - s] = arr[s:e]
        rows = self._allgather_rows(block)
        if self._rank == root:
            for p, (ps, pe) in enumerate(ranges):
                arr[ps:pe] = rows[p, : pe - ps]
        return arr

    def scatter_array(self, arr, operand: Operand = Operands.FLOAT,
                      root: int = 0, ranges=None):
        self._assert_open()
        self._check_root(root)
        arr, _, _ = self._norm_range(arr, operand, 0, None)
        ranges = self._norm_ranges(arr, ranges)
        if self._n == 1:
            return arr
        lo, hi = ranges[0][0], ranges[-1][1]
        full = self._bcast(np.ascontiguousarray(arr[lo:hi]), root)
        s, e = ranges[self._rank]
        arr[s:e] = full[s - lo: e - lo]
        return arr

    def reduce_scatter_array(self, arr, operand: Operand = Operands.FLOAT,
                             operator: Operator = Operators.SUM,
                             ranges=None):
        self._assert_open()
        arr, _, _ = self._norm_range(arr, operand, 0, None)
        ranges = self._norm_ranges(arr, ranges)
        if self._n == 1:
            return arr
        s, e = ranges[self._rank]
        if operator is Operators.SUM:  # identity: custom "SUM" is host
            # device psum_scatter over the (possibly uneven) ranges:
            # pack each range into an identity-padded equal block so
            # shard r's scattered segment IS range r
            B = max(1, max(re - rs for rs, re in ranges))
            blocks = np.full(self._n * B, operator.identity(arr.dtype),
                             dtype=arr.dtype)
            for r, (rs, re) in enumerate(ranges):
                blocks[r * B: r * B + (re - rs)] = arr[rs:re]
            mine = self._device_rows_collective("reduce_scatter", blocks,
                                                operator.lax_collective)
            arr[s:e] = mine[: e - s]
            return arr
        if self._device_reduce_ok(operator):
            # no pmax/pmin-scatter primitive: device allreduce + slice
            lo, hi = ranges[0][0], ranges[-1][1]
            merged = self._device_rows_collective(
                "allreduce", np.ascontiguousarray(arr[lo:hi]),
                operator.lax_collective)
            arr[s:e] = merged[s - lo: e - lo]
            return arr
        lo, hi = ranges[0][0], ranges[-1][1]
        rows = self._allgather_rows(np.ascontiguousarray(arr[lo:hi]))
        merged = self._reduce_rows(rows, operator)
        arr[s:e] = merged[s - lo: e - lo]
        return arr

    # -- map collectives -----------------------------------------------
    # Two planes. The DEVICE plane (numeric operands): key<->code
    # vocabularies kept IDENTICAL on every process (only novel keys
    # ride a small pickled exchange — near-empty once a gradient
    # stream's vocabulary stabilizes) and the values ride ONE device
    # sparse allreduce over the per-process mesh, like the dense plane.
    # The HOST plane (object values, or 64-bit without x64): the
    # pickled whole-map exchange, the reference's Kryo analogue.
    @staticmethod
    def _merge_maps(operator: Operator, acc: dict, src: dict) -> dict:
        # plain per-key loop by measurement — see
        # process_comm._merge_maps
        for k, v in src.items():
            acc[k] = operator.np_fn(acc[k], v) if k in acc else v
        return acc

    def _job_x64(self) -> bool:
        """jax_enable_x64 agreed JOB-WIDE (AND over ranks, pinned):
        a per-host flag divergence would otherwise route ranks onto
        different planes — mismatched programs, a hang. Pinned: flip
        the config before first use."""
        if self._x64_all is None:
            flag = bool(jax.config.jax_enable_x64)
            self._x64_all = (all(self._exchange_obj(flag))
                             if self._n > 1 else flag)
        return self._x64_all

    def _map_device_ok(self, operand: Operand,
                       operator: Operator) -> bool:
        if not operand.is_numeric:
            return False
        if operator not in (Operators.SUM, Operators.MAX,
                            Operators.MIN, Operators.PROD):
            # a custom operator's fn may be host-only python (legal on
            # the per-scalar merge loop); only the BUILTIN objects
            # (equality, not name — a custom named "MAX" is not MAX)
            # are known jit-safe, so customs keep the pickled plane
            return False
        if operand.dtype.itemsize == 8 and not self._job_x64():
            return False
        return True

    def _union_device(self, d: dict, operand: Operand,
                      operator: Operator):
        """The job-wide reduced union via the device plane as
        ``(codec, codes, values)``, or None when every rank's map is
        empty. Codec synchronization: each call, every rank's NOVEL
        keys (plus its entry count, value shape, key kind and any LOCAL
        validation error) ride one pickled exchange; all ranks then
        grow their codec with the same union in the same order, so
        codes agree job-wide without ever exchanging full maps again.

        All local validation (key kinds, value cast/shape) happens
        BEFORE the exchange and its outcome rides it: a bad map on one
        rank must raise on EVERY rank, not error on one while its peers
        block in the device collective."""
        from ytk_mp4j_tpu.comm import keycodec
        from ytk_mp4j_tpu.ops import sparse as sparse_ops

        k0 = next(iter(d)) if d else None
        kind = None if k0 is None else keycodec.kind_of(k0)
        vshape = None if not d else np.shape(d[k0])
        codec = self._codecs_by_kind.get(kind) if kind else None
        if kind and codec is None:
            codec = self._codecs_by_kind[kind] = (
                keycodec.codec_for_kind(kind))
        c = len(d)
        err = None
        novel: list = []
        v = None
        if c:
            try:
                novel = codec.novel(d.keys(), c)
                v = keycodec.pack_values(d.values(), c, vshape,
                                         operand.dtype)
            except Mp4jError as e:
                err = str(e)
        infos = self._exchange_obj((kind, novel, c, vshape, err))
        errs = [i[4] for i in infos if i[4]]
        if errs:
            raise Mp4jError(f"map collective invalid on some rank: "
                            f"{errs[0]}")
        kinds = {i[0] for i in infos if i[0] is not None}
        if len(kinds) > 1:
            raise Mp4jError(
                f"map key kinds differ across ranks: {sorted(kinds)}")
        vshapes = {i[3] for i in infos if i[3] is not None}
        if len(vshapes) > 1:
            raise Mp4jError(
                f"map values must share a shape across ranks; got "
                f"{sorted(vshapes)}")
        total = sum(i[2] for i in infos)
        if total == 0:
            return None
        job_kind = next(iter(kinds))
        vshape = next(iter(vshapes))
        if codec is None:   # this rank was empty: adopt the job's kind
            codec = self._codecs_by_kind.get(job_kind)
            if codec is None:
                codec = self._codecs_by_kind[job_kind] = (
                    keycodec.codec_for_kind(job_kind))
        union_novel = [k for i in infos for k in i[1]]
        if union_novel:
            codec.encode(union_novel, len(union_novel))
        Lmax = keycodec.pow2_bucket(max(1, max(i[2] for i in infos)))
        ident = operator.identity(operand.dtype)
        idx = np.full(Lmax, sparse_ops.SENTINEL, np.int32)
        val = np.full((Lmax,) + vshape, ident, dtype=operand.dtype)
        if c:
            idx[:c] = codec.encode(d.keys(), c)
            val[:c] = v
        cap = keycodec.pow2_bucket(min(codec.size, total))
        oi, ov = self._device_sparse_allreduce(idx, val, cap, operand,
                                               operator)
        live = oi != sparse_ops.SENTINEL
        return codec, oi[live], ov[live]

    def _device_sparse_allreduce(self, idx, val, capacity: int,
                                 operand: Operand, operator: Operator):
        from functools import partial
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ytk_mp4j_tpu.ops import sparse as sparse_ops

        mesh = self._proc_mesh()
        vshape = val.shape[1:]
        key = ("sparse", idx.shape[0], capacity, vshape,
               val.dtype.str, operator.name, id(operator))
        fn = self._djits.get(key)
        if fn is None:
            def body(i, v):
                return sparse_ops.sparse_allreduce(
                    i[0], v[0], capacity, operator, "proc")

            fn = jax.jit(partial(
                jax.shard_map, mesh=mesh, check_vma=False,
                in_specs=(P("proc"), P("proc")),
                out_specs=(P(None), P(None)))(body))
            self._djits[key] = fn
        n = self._n
        gi = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("proc")), idx[None, :],
            (n,) + idx.shape)
        gv = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("proc")), val[None],
            (n,) + val.shape)
        oi, ov = fn(gi, gv)
        # two fetches HERE, unlike the driver backend's single fetch
        # (tpu_comm._union_codes): deriving the union host-side would
        # mean shipping every rank's full code list through the pickled
        # exchange, the O(K) per-call cost this plane exists to avoid
        return (np.asarray(oi.addressable_data(0)),
                np.asarray(ov.addressable_data(0)))

    def _merged_union(self, d: dict, operand: Operand,
                      operator: Operator) -> dict | None:
        """The job-wide merged union dict via whichever plane applies;
        None when the device plane saw every rank empty."""
        if self._map_device_ok(operand, operator):
            out = self._union_device(d, operand, operator)
            if out is None:
                return None
            codec, codes, vals = out
            return dict(zip(codec.decode(codes), list(vals)))
        merged: dict = {}
        for m in self._exchange_obj(d):
            self._merge_maps(operator, merged, m)
        return merged

    def reset_map_vocabularies(self) -> None:
        """Drop the synchronized key<->code vocabularies (see
        ``TpuCommCluster.reset_map_vocabularies`` for why). COLLECTIVE
        in effect: every rank must call it at the same program point —
        a one-sided reset would silently desynchronize codes (this rank
        would re-insert keys its peers already hold under old codes)."""
        self._assert_open()
        self._codecs_by_kind.clear()

    def allreduce_map(self, d: dict, operand: Operand = Operands.DOUBLE,
                      operator: Operator = Operators.SUM) -> dict:
        self._assert_open()
        if self._n == 1:
            return d
        merged = self._merged_union(d, operand, operator)
        if merged is None:
            return d
        d.clear()
        d.update(merged)
        return d

    def reduce_map(self, d: dict, operand: Operand = Operands.DOUBLE,
                   operator: Operator = Operators.SUM, root: int = 0) -> dict:
        self._assert_open()
        self._check_root(root)
        if self._n == 1:
            return d
        merged = self._merged_union(d, operand, operator)
        if merged is None:
            return d
        if self._rank == root:
            d.clear()
            d.update(merged)
        return d

    def broadcast_map(self, d: dict, operand: Operand = Operands.DOUBLE,
                      root: int = 0) -> dict:
        self._assert_open()
        self._check_root(root)
        if self._n == 1:
            return d
        src = self._exchange_obj(d)[root]
        d.clear()
        d.update(src)
        return d

    def gather_map(self, d: dict, operand: Operand = Operands.DOUBLE,
                   root: int = 0) -> dict:
        self._assert_open()
        self._check_root(root)
        if self._n == 1:
            return d
        maps = self._exchange_obj(d)
        union = self._disjoint_union(maps, "gather_map")
        if self._rank == root:
            d.clear()
            d.update(union)
        return d

    @staticmethod
    def _disjoint_union(maps, what: str) -> dict:
        """Disjoint union of per-rank maps; a duplicate raises naming
        the key and both owner ranks (contract parity with the socket
        backend's gather_map; the conflict hunt runs only on the error
        path)."""
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
                            f"{what}: duplicate key {k!r} owned by "
                            f"ranks {seen[k]} and {r}; use reduce_map "
                            f"to combine")
                    seen[k] = r
        return union

    def allgather_map(self, d: dict,
                      operand: Operand = Operands.DOUBLE) -> dict:
        self._assert_open()
        if self._n == 1:
            return d
        maps = self._exchange_obj(d)
        union = self._disjoint_union(maps, "allgather_map")
        d.clear()
        d.update(union)
        return d

    def scatter_map(self, d: dict, operand: Operand = Operands.DOUBLE,
                    root: int = 0, partitioner=None) -> dict:
        """``partitioner(key) -> rank`` overrides the placement rule
        (contract parity with ``ProcessCommSlave.scatter_map``); it must
        be the same function on every rank."""
        self._assert_open()
        self._check_root(root)
        if self._n == 1:
            return d
        if partitioner is None:
            partitioner = lambda k: meta.key_partition(k, self._n)  # noqa: E731
        src = self._exchange_obj(d)[root]
        mine = {}
        for k, v in src.items():
            if meta.check_partition_rank(partitioner(k), self._n,
                                         k) == self._rank:
                mine[k] = v
        d.clear()
        d.update(mine)
        return d

    def reduce_scatter_map(self, d: dict,
                           operand: Operand = Operands.DOUBLE,
                           operator: Operator = Operators.SUM) -> dict:
        self._assert_open()
        if self._n == 1:
            return d
        if self._map_device_ok(operand, operator):
            out = self._union_device(d, operand, operator)
            if out is None:
                return d
            codec, codes, vals = out
            # blake2b placement cached per code on the codec
            mask = codec.partition(codes, self._n) == self._rank
            mine = dict(zip(codec.decode(codes[mask]),
                            list(vals[mask])))
        else:
            acc: dict = {}
            for m in self._exchange_obj(d):
                self._merge_maps(operator, acc, m)
            mine = {k: v for k, v in acc.items()
                    if meta.key_partition(k, self._n) == self._rank}
        d.clear()
        d.update(mine)
        return d

    # ------------------------------------------------------------------
    # nonblocking collectives (ISSUE 11): the multi-host device plane
    # runs one jitted program per collective whose dispatch is already
    # asynchronous under JAX — the i* twins execute eagerly and return
    # resolved futures, keeping one API across all four backends.
    # ------------------------------------------------------------------
    def iallreduce(self, arr, operand: Operand = Operands.FLOAT,
                   operator: Operator = Operators.SUM,
                   from_: int = 0, to: int | None = None):
        """Eager nonblocking :meth:`allreduce_array` (resolved
        future)."""
        return progress_mod.eager_future(
            self, "allreduce_array", arr, operand, operator,
            from_=from_, to=to)

    def ireduce_scatter(self, arr, operand: Operand = Operands.FLOAT,
                        operator: Operator = Operators.SUM,
                        ranges=None):
        """Eager nonblocking :meth:`reduce_scatter_array`."""
        return progress_mod.eager_future(
            self, "reduce_scatter_array", arr, operand, operator,
            ranges=ranges)

    def iallgather(self, arr, operand: Operand = Operands.FLOAT,
                   ranges=None):
        """Eager nonblocking :meth:`allgather_array`."""
        return progress_mod.eager_future(
            self, "allgather_array", arr, operand, ranges=ranges)

    def igather(self, arr, operand: Operand = Operands.FLOAT,
                root: int = 0, ranges=None):
        """Eager nonblocking :meth:`gather_array`."""
        return progress_mod.eager_future(
            self, "gather_array", arr, operand, root=root,
            ranges=ranges)

    def iallreduce_map(self, d: dict,
                       operand: Operand = Operands.DOUBLE,
                       operator: Operator = Operators.SUM):
        """Eager nonblocking :meth:`allreduce_map`."""
        return progress_mod.eager_future(
            self, "allreduce_map", d, operand, operator)

    def wait_all(self, timeout: float | None = None) -> None:
        """Collective-boundary drain; the eager backend never has
        outstanding work — no-op, kept for portable code."""


# per-collective tracing (utils.trace; zero overhead when disabled)
trace.instrument(DistributedComm)
