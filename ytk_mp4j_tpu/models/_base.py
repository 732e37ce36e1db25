"""Shared data-parallel trainer plumbing for the model families.

Every ytk-learn-style consumer here (GBDT, linear, FM/FFM) shards its
samples over the mesh the same way: flat or hierarchical mesh axes, rows
padded up to a multiple of the shard count, padding rows neutralized by a
zero sample weight so distributed results match single-device runs for
any N (SURVEY.md section 4's differential-testing requirement).
"""

from __future__ import annotations

import itertools

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ytk_mp4j_tpu.obs import spans
from ytk_mp4j_tpu.parallel.mesh import make_mesh


def per_example_loss(z, y, loss: str):
    """Per-example data loss shared by the linear and FM/FFM families.

    ``logistic``: softplus-form logloss on {0, 1} labels, written as
    ``max(z, 0) - z y + log1p(exp(-|z|))`` for overflow-free evaluation
    at large |z|. ``squared``: 0.5 (z - y)^2. ``softmax``: cross
    entropy over ``z`` [N, C] with integer labels — the true-class
    logit is selected by a one-hot dot, not a per-row gather (the
    serial gather unit; same choice as the GBDT routing).
    """
    if loss == "logistic":
        return jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    if loss == "softmax":
        lse = jax.nn.logsumexp(z, axis=-1)
        zy = jnp.sum(
            z * jax.nn.one_hot(y, z.shape[-1], dtype=z.dtype), axis=-1)
        return lse - zy
    return 0.5 * (z - y) ** 2


def stage_softmax_labels(y, n_classes: int) -> "np.ndarray":
    """Validate + cast integer class labels, shared by every softmax
    trainer (linear, GBDT): out-of-range ids would one-hot to silent
    garbage, so they must be an error."""
    import numpy as np

    from ytk_mp4j_tpu.exceptions import Mp4jError

    y = np.asarray(y, np.int32)
    if y.size and (y.min() < 0 or y.max() >= n_classes):
        raise Mp4jError(
            f"softmax labels must lie in [0, {n_classes}), got range "
            f"[{y.min()}, {y.max()}]")
    return y


def save_npz(path: str, cfg, arrays: dict) -> None:
    """Model-persistence writer shared by every trainer: the config
    dataclass (repr of asdict, decoded by literal_eval) plus named
    arrays. Writes through a file object so the exact user path is
    honored (np.savez(path) silently appends ".npz"); only process 0
    writes on multi-process jobs."""
    from dataclasses import asdict

    if jax.process_index() != 0:
        return
    with open(path, "wb") as f:
        np.savez(f, config=np.array(repr(asdict(cfg))), **arrays)


def load_npz(path: str, config_cls):
    """Counterpart of :func:`save_npz`: returns (config instance,
    {name: array}) with pickle disabled."""
    import ast

    with np.load(path, allow_pickle=False) as z:
        cfg = config_cls(**ast.literal_eval(str(z["config"])))
        arrays = {k: z[k] for k in z.files if k != "config"}
    return cfg, arrays


class EarlyStopper:
    """The shared early-stopping state machine (GBDT/linear/FM fits).

    ``update(metric, round_idx, state)`` records one round; ``state``
    is an arbitrary rollback payload kept only for the best round and
    only when stopping is enabled (a snapshot can pin large device
    buffers). Returns True when ``rounds`` consecutive non-improving
    rounds have passed. NaN metrics never count as improvements, so a
    NaN-only history leaves ``best_round == -1`` (callers keep
    everything in that case rather than truncating to empty).
    """

    _MIN_DELTA = 1e-12

    def __init__(self, rounds: int | None):
        self.rounds = rounds
        self.best_metric = np.inf
        self.best_round = -1
        self.best_state = None
        self.history: list[float] = []

    def update(self, metric: float, round_idx: int, state=None) -> bool:
        self.history.append(metric)
        if metric < self.best_metric - self._MIN_DELTA:
            self.best_metric, self.best_round = metric, round_idx
            if self.rounds is not None:
                self.best_state = state
            return False
        return (self.rounds is not None
                and round_idx - self.best_round >= self.rounds)


class DataParallelTrainer:
    """Mesh bookkeeping + sample sharding shared by the trainers."""

    def __init__(self, mesh=None, n_devices=None):
        self.mesh = mesh if mesh is not None else make_mesh(n_devices)
        self.axes = (self.mesh.axis_names[0]
                     if len(self.mesh.axis_names) == 1
                     else tuple(self.mesh.axis_names))
        self._row_placers = {}      # the table builders' programs

    @property
    def n_shards(self) -> int:
        return self.mesh.size

    def _row_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axes))

    def _place_replicated(self, tree):
        """Commit a parameter pytree to the mesh, replicated, BEFORE the
        first step call. A jitted step fed uncommitted host arrays
        compiles once for them and AGAIN for its own committed outputs
        on the next call — a duplicate compile of the identical program
        (measured ~8 s for the FFM sparse step at the bench shape).
        device_put is a no-op when the placement already matches."""
        sh = NamedSharding(self.mesh, P())
        return jax.tree_util.tree_map(
            lambda p: jax.device_put(p, sh), tree)

    @staticmethod
    def _replica_on(tree, device):
        """``device``'s copy of every array of a pytree that
        ``_place_replicated`` placed: what a program of that device
        alone takes."""
        return jax.tree_util.tree_map(
            lambda p: next(s.data for s in p.addressable_shards
                           if s.device == device), tree)

    def _pad_rows(self, arrays: list[np.ndarray], weights: bool = True):
        """Pad dim 0 of each array to a multiple of ``n_shards``; returns
        (padded arrays, per-shard rows, sample-weight vector with zeros on
        the padding rows, or None where the caller uses no ``weights``)."""
        N = arrays[0].shape[0]
        n = self.n_shards
        per = -(-N // n)
        pad = per * n - N
        # what the host builds here: the weights, and every array again
        # where the rows do not fill the shards
        built = 4 * per * n if weights else 0
        if pad:
            built += sum(a.nbytes // N * per * n for a in arrays)
        with spans.span("mp4j.stage.prep", bytes=built):
            sw = np.ones(N, np.float32) if weights else None
            if pad:
                arrays = [
                    np.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                    for a in arrays
                ]
                if weights:
                    sw = np.pad(sw, (0, pad))
        return arrays, per, sw

    def _stream_fit(self, batches, stage_chunk, dispatch,
                    batch_rows: int | None, max_in_flight: int):
        """The shared double-buffered streaming loop (FM and linear
        fit_stream): dispatch step k asynchronously, then parse/stage
        chunk k+1 while the device runs it, with at most
        ``max_in_flight`` steps outstanding (the throttle blocks on the
        (k - max_in_flight)-th loss; 0 serializes). Losses are fetched
        once at the end — a per-chunk fetch would block the host on
        every step — and both jnp.stack-then-fetch and
        copy_to_host_async prefixes measured SLOWER than the plain
        device_get (2026-07, previous installation; not measured on
        the present machine).

        ``stage_chunk(chunk, batch_rows) -> (staged, batch_rows)``
        does the host half (validate/pad/placement; resolves
        batch_rows from the first chunk); ``dispatch(staged) -> loss``
        runs the device half, carrying trainer state in its closure.
        Returns the per-chunk loss array."""
        if batch_rows is not None:
            # the padded batch splits evenly over the mesh
            batch_rows = -(-batch_rows // self.n_shards) * self.n_shards
        pending: list = []
        staged = None

        def launch():
            with spans.span("mp4j.stream.dispatch", chunk=len(pending)):
                pending.append(dispatch(staged))

        def chunks():
            # the caller's iterator (reader, parser) under a span of its
            # own, the call that finds it exhausted too
            it, done = iter(batches), object()
            for k in itertools.count():
                with spans.span("mp4j.stream.next", chunk=k):
                    chunk = next(it, done)
                if chunk is done:
                    return
                yield k, chunk

        # every host phase is a span carrying its chunk's index
        for k, chunk in chunks():
            if staged is not None:  # overlap: device runs step k-1
                launch()
                if len(pending) > max_in_flight:
                    # bounds device memory AND queued programs (jax has
                    # no "wait for queue depth" primitive); about a step
                    # long when the device sets the pace, about nothing
                    # when the host does
                    waited = len(pending) - 1 - max_in_flight
                    with spans.span("mp4j.stream.throttle", chunk=waited):
                        jax.block_until_ready(pending[waited])
            with spans.span("mp4j.stream.stage", chunk=k):
                staged, batch_rows = stage_chunk(chunk, batch_rows)
        if staged is not None:
            launch()
        if not pending:
            return np.zeros(0, np.float32)
        with spans.span("mp4j.stream.fetch", chunks=len(pending)):
            return np.asarray(jax.device_get(pending))

    def _pad_stream_rows(self, arrays, batch_rows: int):
        """Pad dim 0 of each chunk array up to ``batch_rows`` (raising
        when the chunk is larger) and build the zero-on-padding sample
        weights; returns (padded arrays, sw, per-shard rows)."""
        from ytk_mp4j_tpu.exceptions import Mp4jError

        N = arrays[0].shape[0]
        if N > batch_rows:
            raise Mp4jError(
                f"chunk of {N} rows exceeds batch_rows={batch_rows}; "
                "raise batch_rows or shrink the reader's chunk size")
        pad = batch_rows - N
        sw = np.ones(N, np.float32)
        if pad:
            arrays = [np.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                      for a in arrays]
            sw = np.pad(sw, (0, pad))
        return arrays, sw, batch_rows // self.n_shards

    @staticmethod
    def _stage_weights(sample_weight, N: int):
        """Validate optional [N] instance weights (ytk-learn's
        per-example weighting); returns 1.0 when absent so callers can
        multiply into the padding sample-weight vector unconditionally.
        The checks mirror binning._check_weights — NaN/negative weights
        would corrupt the weighted-mean steps SILENTLY (NaN losses, or
        sign-flipped gradients), and an all-zero vector trains nothing
        while reporting loss 0. Individual zeros are fine (a zero
        weight excludes the row, like padding)."""
        if sample_weight is None:
            return np.float32(1.0)
        from ytk_mp4j_tpu.exceptions import Mp4jError

        sw = np.asarray(sample_weight, np.float32)
        if sw.shape != (N,):
            raise Mp4jError(
                f"sample_weight must be [N={N}], got {sw.shape}")
        if not np.isfinite(sw).all() or (sw < 0).any():
            raise Mp4jError(
                "sample_weight must be finite and non-negative")
        if N and not (sw > 0).any():
            raise Mp4jError(
                "sample_weight sums to zero: nothing to train on")
        return sw

    def _put_sharded(self, a: np.ndarray, per: int):
        """Reshape [n*per, ...] -> [n, per, ...] and place on the mesh.

        ``make_array_from_callback`` (each process materializes only its
        addressable shards) makes this work unchanged on MULTI-PROCESS
        meshes (jax.distributed), where a plain device_put cannot
        target non-addressable devices for ROW-SHARDED placements like
        this one (fully-REPLICATED placements of host inputs are fine —
        see ``_place_replicated``); the callback path is identical to
        device_put on single-process meshes.

        A shard of ``_ONE_TRANSFER_BYTES`` or more crosses in row chunks
        instead (``_put_in_row_chunks``). This is the table builder (a
        training job reads its table for every tree); a scoring call,
        which reads a row once, takes the same rows from :meth:`_pieces`.

        The ``mp4j.put_sharded`` span's children say what the host waited
        for: ``mp4j.stage.send`` (the hand-over to the runtime, which is
        long where the runtime copies or tiles inline), and on the
        chunked path ``place`` (the placer's launch), ``link_wait`` (a
        chunk crossing) and ``device_wait`` (the device placing one).
        Here ``send`` returns before the array has landed and nothing
        waits for it: when it lands no span of the program's can say."""
        with spans.span("mp4j.put_sharded", bytes=a.nbytes):
            a = a.reshape((self.n_shards, per) + a.shape[1:])
            if a.nbytes // self.n_shards >= self._ONE_TRANSFER_BYTES:
                return self._put_in_row_chunks(a)
            return self._send_whole(a)

    def _send_whole(self, a: np.ndarray):
        """``a`` [n_shards, per, ...] onto the mesh in one transfer."""
        with spans.span("mp4j.stage.send", chunk=0, bytes=a.nbytes):
            return jax.make_array_from_callback(
                a.shape, self._row_sharding(), lambda idx: a[idx])

    def _pieces(self, a: np.ndarray, per: int):
        """The rows ``_put_sharded`` places, for a caller that reads each
        once: yields what :meth:`_crossed` yields, and builds no table.
        An array that crosses in one transfer is one piece, itself
        ([n_shards, per, ...]); a shard of ``_ONE_TRANSFER_BYTES`` or
        more comes in ``_array_cuts``' pieces. The spans are
        ``_put_sharded``'s less ``place``."""
        with spans.span("mp4j.put_sharded", bytes=a.nbytes):
            a = a.reshape((self.n_shards, per) + a.shape[1:])
            if a.nbytes // self.n_shards >= self._ONE_TRANSFER_BYTES:
                yield from self._crossed(self._array_cuts(a))
            else:
                yield 0, self._send_whole(a), None, 0, per, []

    # One host-to-device transfer of 2**32 bytes or more falls off a
    # cliff in this runtime (my chip runs, PR 26, v5e host, int32
    # [1, rows, 968]: 4.07 GB in 0.40 s = 10.1 GB/s, 4.30 GB in 23.3 s =
    # 0.185 GB/s, the idle time named ``MapDmaBuffer``; 4.58 GB as one
    # flat array, as four rows of a [4, ., 968] array or at another
    # width: 21-25 s all the same). A shard that large goes in row
    # chunks; a smaller one is the runtime's to pace (the Higgs bins,
    # 1.23 GB, in chunks: a 2.704 s job 20 ms longer, PR 46).
    _ONE_TRANSFER_BYTES = 2 ** 32
    # The one pace of everything that crosses in pieces (``_crossed``),
    # whether a placer or a scoring program takes them. The host waits
    # for a chunk to have crossed before it sends the one after the
    # next: two in flight cross at 13.5 GB/s; one at a time leaves the
    # link idle between chunks (9.8 GB/s), three share it to no gain.
    # It waits for the device only when ``_CHUNKS_AHEAD`` chunks
    # that have crossed wait for their turn (1.5 GB): this host stops for
    # 110 ms at a time, several times a minute, every process at once,
    # and the device goes on through such a stop only with the chunks
    # that have crossed. The chunks are 128 MiB: the device starts when
    # the first pair has crossed, and how long that takes is all that
    # differs from one job to the next. Chip runs of PR 30, 4.58 GB
    # scored by 500 trees as it crosses, a job in s (quartile distance,
    # ms): waiting for the device three 256 MiB chunks back, which starts
    # three transfers at once and then holds the link to the device's
    # pace, 0.669 (5); 256 MiB, two crossing 0.649 (5); 128 MiB 0.615
    # (1.7); 64 MiB 0.611 (0.3), where the host's loop holds it.
    # PR 46, the same bytes staged alone, ms till the table is there:
    # three 256 MiB chunks back 432.9; this pace 339.9; 64 MiB 331.5;
    # three crossing 342.2. A launch of the placer holds the host 2.5-3
    # ms behind the chunk in flight whether its row comes with the
    # launch, was on the device before the loop or is a counter the
    # placer keeps (339.9, 346.8, 340.8): the link sets the pace.
    _EACH_CHUNK_BYTES = 128 * 2 ** 20
    _CHUNKS_CROSSING = 2
    _CHUNKS_AHEAD = 12

    def _crossed(self, cuts):
        """The one pacing loop. ``cuts`` yields ``(send, bytes, shard,
        start, stop)``: ``send()`` hands the runtime rows ``start`` to
        ``stop`` of shard ``shard`` (None: of every shard) and returns
        the device array (or a tuple of them). Yields ``(chunk, piece,
        shard, start, stop, turns)`` as soon as the piece is on its way:
        the consumer launches what reads it (a placer, a scoring
        program), appends to ``turns`` something small that is there
        when the device has had the piece, and keeps no reference to
        it. Then the host waits, at the constants' pace, for the piece
        before the last to have crossed (``link_wait``), which it lets
        go, and for the oldest turn once more than ``_CHUNKS_AHEAD`` are
        outstanding (``device_wait``)."""
        crossing, turns = [], []
        for k, (send, nbytes, shard, start, stop) in enumerate(cuts):
            with spans.span("mp4j.stage.send", chunk=k, bytes=nbytes):
                piece = send()
            yield k, piece, shard, start, stop, turns
            crossing.append(piece)
            del piece
            if len(crossing) >= self._CHUNKS_CROSSING:
                with spans.span("mp4j.stage.link_wait",
                                chunk=k + 1 - len(crossing)):
                    jax.block_until_ready(crossing.pop(0))
            if len(turns) > self._CHUNKS_AHEAD:
                with spans.span("mp4j.stage.device_wait",
                                chunk=k + 1 - len(turns)):
                    jax.block_until_ready(turns.pop(0))

    def _rows_a_piece(self, per: int, row_bytes: int) -> int:
        """Rows of every shard a piece of ``_array_cuts`` holds: what
        fits ``_EACH_CHUNK_BYTES``, in whole rows of 128 lanes."""
        rows = max(1, min(per, self._EACH_CHUNK_BYTES // row_bytes))
        return rows - rows % 128 if rows >= 128 else rows

    def _array_cuts(self, a):
        """``_crossed``'s cuts of ``a`` [n_shards, per, ...] held whole
        by the host: ``_rows_a_piece`` rows of every shard a piece, each
        a row-sharded array that crosses as [n_shards, M, 128]. That
        rests on the device in the order the host holds it, so the host's
        runtime has nothing to transpose and the device puts the piece
        into its reader's layout while the next one crosses. My chip
        runs, PR 26, the 4.58 GB Bosch table: 0.425 s (0.4243-0.4253
        over four) against 0.470 s (0.466-0.482 over ten) when each
        chunk crossed in its own shape and the host's threads tiled it;
        whole jobs of 8 trees, six each in one process, 10.014 s with a
        quartile distance of 0.023 against 10.081 s and 0.093. A piece
        whose elements do not fill rows of 128 crosses in its own shape.
        The last piece is as long as the others: it starts early, over
        rows the piece before it brought.

        A tuple ``a`` of arrays is cut alike, a piece the tuple of its
        pieces, each crossing on its own: the host copies nothing
        (``FMTrainer.predict``'s ids, fields and values: packed on the
        host, 134 MB a chunk took it 235 ms, longer than the device took
        to score the chunk; my chip run, PR 36)."""
        parts = a if isinstance(a, tuple) else (a,)
        n, per = parts[0].shape[:2]
        cols = [int(np.prod(p.shape[2:])) for p in parts]
        rows = self._rows_a_piece(per, sum(cols) * parts[0].itemsize)
        wires = [(n, rows * c // 128, 128) if rows * c % 128 == 0
                 else (n, rows) + p.shape[2:] for c, p in zip(cols, parts)]
        sharding = self._row_sharding()
        for start in range(0, per, rows):
            start = min(start, per - rows)
            chunks = [p[:, start:start + rows] for p in parts]

            def send(chunks=chunks):
                sent = tuple(
                    jax.make_array_from_callback(
                        wire, sharding,
                        lambda idx, chunk=chunk, wire=wire: chunk[
                            idx[0]].reshape((-1,) + wire[1:]))
                    for chunk, wire in zip(chunks, wires))
                return sent if isinstance(a, tuple) else sent[0]

            yield (send, sum(c.nbytes for c in chunks), None, start,
                   start + rows)

    def _put_in_row_chunks(self, a: np.ndarray):
        """``a`` [n_shards, per, ...] onto the mesh, rows sharded, a
        piece of rows at a time (``_array_cuts``, at ``_crossed``'s
        pace): a ``dynamic_update_slice`` places each piece in the
        donated table while the next ones are on their way; the table is
        never held twice."""
        n, per = a.shape[:2]
        rows = self._rows_a_piece(
            per, int(np.prod(a.shape[2:])) * a.itemsize)
        key = (a.shape, a.dtype.str, rows)
        # one program a (table, chunk) shape, kept with the trainer: a
        # job after the first builds nothing
        place = self._row_placers.get(key)
        if place is None:
            with spans.span("mp4j.step.build", key="row_placer",
                            rows=rows):
                place = self._row_placers[key] = self._build_row_placer(
                    (n, rows) + a.shape[2:])
        table = jnp.zeros(a.shape, a.dtype, device=self._row_sharding())
        for k, piece, _, start, _, turns in self._crossed(
                self._array_cuts(a)):
            with spans.span("mp4j.stage.place", chunk=k):
                table, done = place(table, piece, np.int32(start))
            turns.append(done)
        return table

    def _build_row_placer(self, shape):
        """``_put_in_row_chunks``' program: a chunk as it crossed into the
        donated table at a row it is told; also returns the chunk's first
        word, which is there when the chunk has been placed. ``shape``:
        the chunk's in the table."""
        def place(table, chunk, start):
            # the device's side of ``mp4j.stage.place``, by name in a trace
            with jax.named_scope("stage.place"):
                at = [jnp.zeros((), start.dtype)] * table.ndim
                at[1] = start
                return (jax.lax.dynamic_update_slice(
                    table, chunk.reshape(shape), at),
                        chunk.reshape(-1)[0])
        return jax.jit(place, donate_argnums=0,
                       out_shardings=(self._row_sharding(), None))

    def _shard_devices(self, shape) -> dict:
        """shard -> the device that holds it (this process's only) of a
        row-sharded array of ``shape`` [n_shards, ...]."""
        return {index[0].start or 0: device for device, index in
                self._row_sharding().addressable_devices_indices_map(
                    shape).items()}

    def _reader_cuts(self, chunks, n_rows: int, width: int):
        """``_crossed``'s cuts of rows that arrive a chunk at a time,
        each ``[m, width]`` f32 in the order of a table ``[n_shards,
        rows a shard, width]`` sharded as ``_put_sharded`` shards one
        (row r is row ``r % per`` of shard ``r // per``), for a caller (a
        file's reader) who never holds the table. A chunk is cut where it
        spans two shards and evenly into pieces of ``_EACH_CHUNK_BYTES``
        at most; a piece goes to the device that holds its shard
        (another process's rows are passed over), as ``[M, 128]`` words
        where its cells fill them (the host tiles nothing). The rows
        that pad the last shard are no piece.

        A chunk of another width, or chunks that do not add up to
        ``n_rows``, raise: nothing is padded in silence. The iterator's
        arrays must stay as they are until the last piece has been
        taken (a reader that refills one buffer has to yield copies).
        The caller's iterator runs under ``mp4j.stream.next``."""
        from ytk_mp4j_tpu.exceptions import Mp4jError

        per = max(1, -(-n_rows // self.n_shards))
        where = self._shard_devices((self.n_shards, per, width))
        piece_rows = max(1, self._EACH_CHUNK_BYTES // (4 * width))
        done = 0
        it, end = iter(chunks), object()
        for k in itertools.count():
            with spans.span("mp4j.stream.next", chunk=k):
                chunk = next(it, end)
            if chunk is end:
                break
            chunk = np.ascontiguousarray(chunk, np.float32)
            if chunk.ndim != 2 or chunk.shape[1] != width:
                raise Mp4jError(
                    f"chunk {k} must be [rows, {width}], got "
                    f"{chunk.shape}")
            if done + len(chunk) > n_rows:
                raise Mp4jError(
                    f"chunk {k} brings the rows to "
                    f"{done + len(chunk)}, more than n_rows={n_rows}")
            # pieces: cut at shard ends, then evenly under the cap
            at = 0
            while at < len(chunk):
                shard, start = divmod(done + at, per)
                m = min(len(chunk) - at, per - start)
                parts = -(-m // piece_rows)
                m = min(m, -(-m // parts))
                piece = chunk[at:at + m]
                at += m
                if shard in where:          # not another process's rows
                    wire = ((m * width // 128, 128)
                            if m * width % 128 == 0 else (m, width))
                    yield (lambda piece=piece, wire=wire, shard=shard:
                           jax.device_put(piece.reshape(wire), where[shard]),
                           piece.nbytes, shard, start, start + m)
            done += len(chunk)
        if done != n_rows:
            raise Mp4jError(
                f"the chunks hold {done} rows, n_rows={n_rows} were "
                f"announced")

    def _reader_pieces(self, chunks, n_rows: int, width: int):
        """``_reader_cuts``' pieces as they crossed, at ``_crossed``'s
        pace (the ledger's notes of PR 43 have 4.58 GB of floats at
        0.339-0.341 s so), under one ``mp4j.put_sharded`` whose
        ``bytes`` are the announced table's, built or not; under it
        ``mp4j.stream.next`` and ``mp4j.stage.send`` / ``link_wait`` /
        ``device_wait`` a piece, and what the consumer adds."""
        with spans.span("mp4j.put_sharded", bytes=4 * n_rows * width):
            yield from self._crossed(
                self._reader_cuts(chunks, n_rows, width))

    def _put_row_chunks(self, chunks, n_rows: int, width: int):
        """The table builder over ``_reader_pieces``: the rows onto the
        mesh as ``[n_shards, rows a shard, width]`` f32, what
        ``_put_in_row_chunks`` does with an array it holds whole. The
        table is allocated once from ``n_rows``, every cell NaN, so the
        rows that pad the last shard are empty, and a donated
        ``dynamic_update_slice`` places each piece on the device it went
        to (span ``mp4j.stage.place``): never held twice. One placer a
        (shard, piece) shape, kept with the trainer."""
        n = self.n_shards
        per = max(1, -(-n_rows // n))
        shape = (n, per, width)
        tables = {s: jnp.full((1, per, width), jnp.nan, jnp.float32,
                              device=d)
                  for s, d in self._shard_devices(shape).items()}
        for k, piece, shard, start, stop, turns in self._reader_pieces(
                chunks, n_rows, width):
            with spans.span("mp4j.stage.place", chunk=k):
                tables[shard], marker = self._row_chunk_placer(
                    per, width, stop - start, piece.shape)(
                        tables[shard], piece, np.int32(start))
            turns.append(marker)
        return jax.make_array_from_single_device_arrays(
            shape, self._row_sharding(),
            [tables[s] for s in sorted(tables)])

    def _row_chunk_placer(self, per: int, width: int, rows: int, wire):
        """The program that puts a piece of ``rows`` rows, crossed as
        ``wire``, into one shard of ``_put_row_chunks``'s table at a row
        it is told, the table donated; also returns the piece's first
        word, which is there when the piece has been placed."""
        key = ("rows", per, width, rows)
        place = self._row_placers.get(key)
        if place is None:
            def place(table, chunk, start):
                with jax.named_scope("stage.place"):
                    zero = jnp.zeros((), start.dtype)
                    return (jax.lax.dynamic_update_slice(
                        table, chunk.reshape(1, rows, width),
                        (zero, start, zero)), chunk.reshape(-1)[0])

            with spans.span("mp4j.step.build", key="row_chunk_placer",
                            rows=rows):
                place = self._row_placers[key] = jax.jit(
                    place, donate_argnums=0)
        return place

    def save_params(self, path: str, params) -> None:
        """Persist a flat tuple of parameter arrays + the trainer config
        as a portable .npz (the train-then-serve flow; the GBDT trainer
        has its own tree-structured save_model)."""
        # _to_host is COLLECTIVE on multi-process meshes (params may
        # span non-addressable devices): every process must reach it
        # before the process-0 write gate inside save_npz
        arrays = {f"p_{i}": self._to_host(p)
                  for i, p in enumerate(params)}
        save_npz(path, self.cfg, arrays)

    @staticmethod
    def load_params(path: str, config_cls):
        """Load (config, params tuple) saved by :meth:`save_params`;
        ``config_cls`` is the trainer's config dataclass."""
        cfg, arrays = load_npz(path, config_cls)
        return cfg, tuple(arrays[f"p_{i}"] for i in range(len(arrays)))

    @classmethod
    def _local_values(cls, tree):
        """Make every array in a pytree usable in a plain (local) jit:
        arrays spanning non-addressable devices (multi-process meshes)
        are fetched via the collective ``_to_host``; everything else
        passes through untouched. Used by the per-step eval paths."""
        return jax.tree_util.tree_map(
            lambda p: (cls._to_host(p)
                       if not getattr(p, "is_fully_addressable", True)
                       else p), tree)

    @staticmethod
    def _to_host(x) -> np.ndarray:
        """Fetch a (possibly cross-process-sharded) device array to a
        host numpy array on EVERY process. Host numpy inputs (e.g.
        params straight from :meth:`load_params`) pass through."""
        if isinstance(x, np.ndarray) or not hasattr(
                x, "is_fully_addressable"):
            return np.asarray(x)
        if x.is_fully_addressable:
            return np.asarray(x)
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
