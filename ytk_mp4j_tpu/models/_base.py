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


def packed_width(words: int) -> int:
    """32-bit words in a row of a table that ``_put_in_row_chunks`` packs
    out of several arrays: ``words`` rounded up to whole 8s. A width of
    whole 8s rests on the TPU as [width, rows] in (8, 128) tiles, and a
    program slices a tile of rows out of it as it lies; any other width
    rests in (1, 128) tiles and the program copies all of it before it
    reads a row (AOT for v5e, 6,042,135 x 117: 3.03 GB of temporaries a
    call, none at 120)."""
    return -(-words // 8) * 8


class DataParallelTrainer:
    """Mesh bookkeeping + sample sharding shared by the trainers."""

    def __init__(self, mesh=None, n_devices=None):
        self.mesh = mesh if mesh is not None else make_mesh(n_devices)
        self.axes = (self.mesh.axis_names[0]
                     if len(self.mesh.axis_names) == 1
                     else tuple(self.mesh.axis_names))
        self._row_placers = {}      # _put_in_row_chunks' programs

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

    def _put_sharded(self, a: np.ndarray, per: int, each=None):
        """Reshape [n*per, ...] -> [n, per, ...] and place on the mesh.

        ``make_array_from_callback`` (each process materializes only its
        addressable shards) makes this work unchanged on MULTI-PROCESS
        meshes (jax.distributed), where a plain device_put cannot
        target non-addressable devices for ROW-SHARDED placements like
        this one (fully-REPLICATED placements of host inputs are fine —
        see ``_place_replicated``); the callback path is identical to
        device_put on single-process meshes.

        A shard of ``_ONE_TRANSFER_BYTES`` or more crosses in row chunks
        instead (``_put_in_row_chunks``).

        ``each(table, start, stop)``, where given, is called as soon as
        rows [start, stop) of every shard are on their way into
        ``table`` (the array that holds them; it is only good until the
        next call): after every chunk, or once for the whole shard. What
        it dispatches on those rows runs while the rest crosses.

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
                return self._put_in_row_chunks(a, each)
            with spans.span("mp4j.stage.send", chunk=0, bytes=a.nbytes):
                table = jax.make_array_from_callback(
                    a.shape, self._row_sharding(), lambda idx: a[idx])
            if each is not None:
                each(table, 0, per)
            return table

    # One host-to-device transfer of 2**32 bytes or more falls off a
    # cliff in this runtime (my chip runs, PR 26, v5e host, int32
    # [1, rows, 968]: 4.07 GB in 0.40 s = 10.1 GB/s, 4.30 GB in 23.3 s =
    # 0.185 GB/s, the idle time named ``MapDmaBuffer``; 4.58 GB as one
    # flat array, as four rows of a [4, ., 968] array or at another
    # width: 21-25 s all the same). A shard that large goes in row
    # chunks; a smaller one is the runtime's to pace (the Higgs bins,
    # 1.23 GB, in chunks: a 2.704 s job 20 ms longer, PR 46).
    _ONE_TRANSFER_BYTES = 2 ** 32
    # The one pace of a table that crosses in chunks, with work
    # dispatched on every chunk (``each``) or with none. The host waits
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

    def _put_in_row_chunks(self, a: np.ndarray, each=None):
        """``a`` [n_shards, per, ...] onto the mesh, rows sharded, a chunk
        of rows at a time: a ``dynamic_update_slice`` places each chunk
        in the donated table while the next ones are on their way, at
        the one pace the constants above set; the table is never held
        twice. ``each``, where given, is called for every chunk as it is
        placed (``_put_sharded``).

        A chunk crosses as [n_shards, M, 128], which rests on the device
        in the order the host holds it, so the host's runtime has nothing
        to transpose and the device puts the chunk into the table's
        layout while the next one crosses. My chip runs, PR 26, the
        4.58 GB Bosch table: 0.425 s (0.4243-0.4253 over four) against
        0.470 s (0.466-0.482 over ten) when each chunk crossed in its
        own shape and the host's threads tiled it; whole jobs of 8
        trees, six each in one process, 10.014 s with a quartile
        distance of 0.023 against 10.081 s and 0.093. A chunk whose
        elements do not fill rows of 128 crosses in its own shape.

        A tuple ``a`` of arrays of 32-bit elements, each [n_shards, per,
        columns], is staged as ONE table of int32 words, a row the
        arrays' rows side by side (:func:`packed_width` words, the last
        ones zero): every array crosses on its own, a chunk of rows at a
        time and as the host holds it, and the placer puts the chunks'
        rows together on the device. The host copies nothing
        (``FMTrainer.predict`` stages ids, fields and values so: packed
        on the host, 134 MB a chunk took it 235 ms, longer than the
        device took to score the chunk; my chip run, PR 36)."""
        packed = isinstance(a, tuple)
        parts = a if packed else (a,)
        n, per = parts[0].shape[:2]
        cols = [int(np.prod(p.shape[2:])) for p in parts]
        row = sum(cols)                         # elements a row
        rows = max(1, min(per, self._EACH_CHUNK_BYTES
                          // (row * parts[0].itemsize)))
        if rows >= 128:
            rows -= rows % 128                  # whole rows of 128 lanes
        shapes = [(n, rows) + p.shape[2:] for p in parts]
        wires = [(n, rows * c // 128, 128) if rows * c % 128 == 0 else shape
                 for c, shape in zip(cols, shapes)]
        shape = shapes[0]
        sharding = self._row_sharding()
        if packed:
            width = packed_width(row)
            table_shape, dtype = (n, per, width), np.dtype(np.int32)
            key = (table_shape, tuple(p.dtype.str for p in parts), rows)
        else:
            table_shape, dtype = a.shape, a.dtype
            key = (a.shape, a.dtype.str, rows)
        # one program a (table, chunk) shape, kept with the trainer: a
        # job after the first builds nothing
        place = self._row_placers.get(key)
        if place is None:
            with spans.span("mp4j.step.build", key="row_placer",
                            rows=rows):
                place = self._row_placers[key] = self._build_row_placer(
                    shape, width - row if packed else None)
        table = jnp.zeros(table_shape, dtype, device=sharding)
        placed, crossing = [], []
        for k, start in enumerate(range(0, per, rows)):
            # the last chunk is as long as the others: it starts early
            # and rewrites rows the chunk before it already placed
            start = min(start, per - rows)
            chunks = [p[:, start:start + rows] for p in parts]
            with spans.span("mp4j.stage.send", chunk=k,
                            bytes=sum(c.nbytes for c in chunks)):
                dchunks = tuple(
                    jax.make_array_from_callback(
                        wire, sharding,
                        lambda idx, chunk=chunk, wire=wire: chunk[
                            idx[0]].reshape((-1,) + wire[1:]))
                    for chunk, wire in zip(chunks, wires))
            dchunk = dchunks if packed else dchunks[0]
            with spans.span("mp4j.stage.place", chunk=k):
                table, done = place(table, dchunk, np.int32(start))
            placed.append(done)
            crossing.append(dchunk)
            if each is not None:
                each(table, start, start + rows)
            if len(crossing) >= self._CHUNKS_CROSSING:
                with spans.span("mp4j.stage.link_wait",
                                chunk=k + 1 - len(crossing)):
                    jax.block_until_ready(crossing.pop(0))
            if len(placed) > self._CHUNKS_AHEAD:
                with spans.span("mp4j.stage.device_wait",
                                chunk=k + 1 - len(placed)):
                    jax.block_until_ready(placed.pop(0))
        return table

    def _build_row_placer(self, shape, pad: int | None = None):
        """``_put_in_row_chunks``' program: a chunk as it crossed into the
        donated table at a row it is told; also returns the chunk's first
        word, which is there when the chunk has been placed. ``shape``:
        the chunk's in the table; ``pad``: a tuple's zero words a row."""
        n, rows = shape[:2]

        def place(table, chunk, start):
            # the device's side of ``mp4j.stage.place``, by name in a trace
            with jax.named_scope("stage.place"):
                if pad is not None:
                    words = [jax.lax.bitcast_convert_type(
                        c.reshape(n, rows, -1), jnp.int32) for c in chunk]
                    words.append(jnp.zeros((n, rows, pad), jnp.int32))
                at = [jnp.zeros((), start.dtype)] * table.ndim
                at[1] = start
                piece, first = (
                    (chunk.reshape(shape), chunk) if pad is None
                    else (jnp.concatenate(words, axis=2), chunk[0]))
                return (jax.lax.dynamic_update_slice(table, piece, at),
                        first.reshape(-1)[0])
        return jax.jit(place, donate_argnums=0,
                       out_shardings=(self._row_sharding(), None))

    def _put_row_chunks(self, chunks, n_rows: int, width: int, each=None):
        """Rows that arrive a chunk at a time, each ``[m, width]`` f32 in
        the order of the table, onto the mesh as ``[n_shards, rows a
        shard, width]`` f32, rows sharded as ``_pad_rows`` and
        ``_put_sharded`` shard them (row r is row ``r % per`` of shard
        ``r // per``): what ``_put_in_row_chunks`` does with an array it
        holds whole, for a caller (a file's reader) who never holds one.
        The table is allocated once from ``n_rows``, every cell NaN, so
        the rows that pad the last shard are empty; a chunk crosses as
        it arrives, as ``[M, 128]`` words where its cells fill them (the
        host tiles nothing), and a donated ``dynamic_update_slice``
        places it, so the table is never held twice. A chunk goes to the
        device that holds its rows, cut where it spans two shards and
        into pieces of ``_EACH_CHUNK_BYTES`` at most, two crossing at a
        time and up to ``_CHUNKS_AHEAD`` waiting to be placed
        (``_put_in_row_chunks``' pace; the ledger's notes of PR 43 have
        4.58 GB of floats at 0.339-0.341 s so). One placer a (shard,
        piece) shape, kept with the trainer.

        ``each(table, start, stop)``, where given, is ``_put_sharded``'s
        hand-over: called as soon as rows [start, stop) of EVERY shard
        are on their way into ``table`` (the whole array; only good
        until the next call), so that what it dispatches on them runs
        while the next pieces cross. The rows arrive in the table's
        order, shard after shard, so that is after every piece of the
        last shard (of the only one, on one device: after every piece),
        and once more at the end for the rows the last shard's data does
        not reach (its padding). Every row of a shard is handed over
        exactly once.

        A chunk of another width, or chunks that do not add up to
        ``n_rows``, raise: nothing is padded in silence. The iterator's
        arrays must stay as they are until the table is returned (a
        reader that refills one buffer has to yield copies).

        The spans are ``_put_sharded``'s: one ``mp4j.put_sharded``
        (``bytes``: the table's, known from ``n_rows``) round the loop;
        under it ``mp4j.stream.next`` (the caller's iterator),
        ``mp4j.stage.send`` / ``place`` / ``link_wait`` /
        ``device_wait`` a piece."""
        from ytk_mp4j_tpu.exceptions import Mp4jError

        n = self.n_shards
        per = max(1, -(-n_rows // n))
        sharding = self._row_sharding()
        shape = (n, per, width)
        # shard -> the device that holds it (this process's only)
        where = {index[0].start or 0: device for device, index in
                 sharding.addressable_devices_indices_map(shape).items()}
        tables = {s: jnp.full((1, per, width), jnp.nan, jnp.float32,
                              device=d) for s, d in where.items()}
        piece_rows = max(1, self._EACH_CHUNK_BYTES // (4 * width))
        placed, crossing = [], []
        done, sent = 0, 0
        handed = 0                  # rows of every shard ``each`` has had

        def whole():
            return jax.make_array_from_single_device_arrays(
                shape, sharding, [tables[s] for s in sorted(tables)])

        it, end = iter(chunks), object()
        with spans.span("mp4j.put_sharded", bytes=4 * n_rows * width):
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
                        with spans.span("mp4j.stage.send", chunk=sent,
                                        bytes=piece.nbytes):
                            dpiece = jax.device_put(piece.reshape(wire),
                                                    where[shard])
                        with spans.span("mp4j.stage.place", chunk=sent):
                            tables[shard], marker = self._row_chunk_placer(
                                per, width, m, wire)(
                                    tables[shard], dpiece, np.int32(start))
                        placed.append(marker)
                        crossing.append(dpiece)
                        sent += 1
                    if each is not None and shard == n - 1:
                        each(whole(), start, start + m)
                        handed = start + m
                    if len(crossing) >= self._CHUNKS_CROSSING:
                        with spans.span("mp4j.stage.link_wait",
                                        chunk=sent - len(crossing)):
                            jax.block_until_ready(crossing.pop(0))
                    if len(placed) > self._CHUNKS_AHEAD:
                        with spans.span("mp4j.stage.device_wait",
                                        chunk=sent - len(placed)):
                            jax.block_until_ready(placed.pop(0))
                done += len(chunk)
            if done != n_rows:
                raise Mp4jError(
                    f"the chunks hold {done} rows, n_rows={n_rows} were "
                    f"announced")
            if each is not None and handed < per:
                each(whole(), handed, per)
            return whole()

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
