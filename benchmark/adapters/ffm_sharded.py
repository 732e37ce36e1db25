"""Drives ``FMTrainer.fit_stream()`` with the table sharded by feature over
the cell's chips (``table_sharding="sharded"``): one call consumes
minibatches until the window's seconds have elapsed, as
``adapters/ffm.py`` does on one chip with a replicated table.

Only the trainer's public surface is used: the constructor, its ``mesh``,
``n_rows_padded``, ``fit_stream(batches, params=, batch_rows=,
max_in_flight=)`` and ``exchange_rounds_``, the rounds of the step's
exchange that the last call ran. The public table ``[n_rows_padded, k]``
is made on the devices from the seed already cut over the mesh (every
member draws the rows of the features it owns; no chip ever holds more
than its share, and the host none of it), and what the check needs of it
is gathered from where it rests: every member answers for the rows it
holds and a sum brings them together.

``fit_stream`` keeps the table it was given until it returns, and its
conversion holds the public table and the step's side by side (13.8 GB a
chip at 2^25 features), so the adapter never holds a table across a
call: the parameters live in ``self._params`` only between calls
(``_take``).

A program whose sharded step is not in the block form (the parent of the
PR that added this cell: a row a slot pair, every member's requests
gathered by every owner, no ``all_to_all`` in its library) is refused in
``setup`` before anything is made: at this size it would not fit.
"""

from __future__ import annotations

import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import traffic as traffic_gen
from benchmark.reference import ffm_sharded as reference
from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

UNTOUCHED_FEATURES = 4096     # features the chunk lacks, every row of theirs


def _member(axes):
    """Row-major index of this member over the mesh's axes."""
    idx = lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def params_maker(config, trainer):
    """A jitted ``key -> (w0, w, table)``: the public parameters made on
    the devices in the placement ``fit_stream`` takes them (the table's
    rows cut over the mesh, the rest replicated), so its own placing is a
    no-op. Every member draws its own rows, a piece at a time written in
    place: the program's temporaries are a piece, not a shard."""
    mesh = trainer.mesh
    axes = tuple(mesh.axis_names)
    n_fields, k = config["n_fields"], config["k"]
    scale = config["init_scale"]
    mine = trainer.n_rows_padded // mesh.size       # rows a member holds
    piece = mine // n_fields

    @partial(jax.shard_map, mesh=mesh, in_specs=P(), out_specs=P(axes),
             check_vma=False)
    def table(key):
        key = jax.random.fold_in(key, _member(axes))

        def block(i, rows):
            drawn = scale * jax.random.normal(
                jax.random.fold_in(key, i), (piece, k), jnp.float32)
            return lax.dynamic_update_slice_in_dim(rows, drawn, i * piece,
                                                   axis=0)

        return lax.fori_loop(0, n_fields, block,
                             jnp.zeros((mine, k), jnp.float32))

    def make_params(key):
        return (jnp.zeros((), jnp.float32),
                jnp.zeros((config["n_features"],), jnp.float32), table(key))

    rep = NamedSharding(mesh, P())
    return jax.jit(make_params,
                   out_shardings=(rep, rep, NamedSharding(mesh, P(axes))))


def rows_gatherer(trainer):
    """A jitted ``(table, rows) -> table[rows]`` for the table as it
    rests, rows cut over the mesh: every member gathers what it holds of
    ``rows`` (replicated) and a sum over the members fills in the rest.
    The table itself never moves."""
    mesh = trainer.mesh
    axes = tuple(mesh.axis_names)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axes), P()),
             out_specs=P(), check_vma=False)
    def gather(held, rows):
        low = _member(axes) * held.shape[0]
        mine = (rows >= low) & (rows < low + held.shape[0])
        got = held[jnp.where(mine, rows - low, 0)]
        return lax.psum(jnp.where(mine[..., None], got, 0.0), axes)

    return jax.jit(gather)


def _excess(err, allowed) -> float:
    """Largest error as a share of what is allowed: <= 1 passes."""
    return float(np.max(np.abs(err) / allowed, initial=0.0))


class Adapter:
    def __init__(self, config, traffic, seed, devices, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.spans = devices, spans
        self._params = None
        self.losses = []
        self.rounds = []            # exchange rounds of every fit_stream

    def setup(self):
        c, t = self.config, self.traffic
        # first thing: the parent's program fails here, in seconds
        from ytk_mp4j_tpu.ops import collectives
        if not (hasattr(collectives, "all_to_all")
                and hasattr(FMTrainer, "n_features_padded")):
            raise RuntimeError(
                "this program's sharded FFM step is not the block form "
                "(ops/collectives has no all_to_all, the table is not cut "
                "by feature): the cell needs it")
        self.cfg = FMConfig(
            model=c["model"], n_features=c["n_features"],
            n_fields=c["n_fields"], k=c["k"], max_nnz=c["max_nnz"],
            loss=c["loss"], learning_rate=c["learning_rate"], l2=c["l2"],
            init_scale=c["init_scale"])
        self.trainer = FMTrainer(
            self.cfg, n_devices=len(self.devices),
            sparse_grads=c["sparse_grads"],
            table_sharding=c["table_sharding"])
        with self.spans.span("ffm.make_pool"):
            self.pool = traffic_gen.zipf_chunk_pool(
                self.seed, c["n_features"], c["n_fields"],
                t["rows_per_chunk"], t["pool_chunks"], t["zipf_exponent"],
                t["positive_rate"])
        with self.spans.span("ffm.count_pool"):
            self.pool_counts = self._count_pool()
        make_params = params_maker(c, self.trainer)
        with self.spans.span("ffm.make_table"):
            self._params = make_params(jax.random.key(self.seed))
            jax.block_until_ready(self._params)
        self._gather_rows = rows_gatherer(self.trainer)
        self._gather = jax.jit(lambda w, ids: w[ids])

    def _count_pool(self) -> dict:
        """What each chunk of the pool asks of the exchange, from the ids
        alone: a chunk's rows go to the chips in equal runs, a feature
        belongs to chip ``id // (padded features / chips)``. Per chunk:
        ``distinct_features`` (distinct ids a chip holds, summed over the
        chips), ``remote_blocks`` (those of them the chip does not own)
        and ``owner_load`` (blocks asked of the fullest owner over the
        mean owner's)."""
        n = len(self.devices)
        per_owner = self.trainer.n_rows_padded // self.cfg.n_fields // n
        distinct, remote, load = [], [], []
        for feats, *_ in self.pool:
            asked = np.zeros(n)
            d = r = 0
            for chip, part in enumerate(np.split(feats, n)):
                owner = np.unique(part) // per_owner
                d += owner.size
                r += int(np.count_nonzero(owner != chip))
                asked += np.bincount(owner, minlength=n)
            distinct.append(d)
            remote.append(r)
            load.append(asked.max() / asked.mean())
        return {"distinct_features": np.asarray(distinct, np.float64),
                "remote_blocks": np.asarray(remote, np.float64),
                "owner_load": np.asarray(load, np.float64)}

    def _take(self):
        params, self._params = self._params, None
        return params

    def _fit(self, chunks):
        """``fit_stream`` over ``chunks`` from the current parameters;
        returns the per-chunk losses."""
        t = self.traffic
        self._params, losses = self.trainer.fit_stream(
            chunks, params=self._take(), batch_rows=t["rows_per_chunk"],
            max_in_flight=t["max_in_flight"])
        self.rounds.append(self.trainer.exchange_rounds_)
        return losses

    def _slot_rows(self, chunk):
        """Table row of every slot pair: v[feat_a, field_b] is row
        ``feat_a * n_fields + field_b``; [N, K, K]."""
        feats, fields = chunk[:2]
        return feats[:, :, None] * self.cfg.n_fields + fields[:, None, :]

    def _rows_of(self, rows):
        """``table[rows]`` on the host for a sorted set of rows: one
        gather program, the set padded to the chunk's slot pairs."""
        n = self.pool[0][0].size * self.cfg.max_nnz
        part = np.zeros(n, rows.dtype)
        part[: rows.size] = rows
        return np.asarray(self._gather_rows(self._params[2], part))[
            : rows.size]

    def warmup(self):
        """The first chunk alone (compiles the step and both conversions;
        its inputs and outputs are fetched for the check), then two more
        through the pipelined loop. The window starts from the table this
        leaves."""
        c, nf = self.cfg, self.cfg.n_fields
        feats = self.pool[0][0]
        rows = self._slot_rows(self.pool[0])
        uniq, ufeat = np.unique(rows), np.unique(feats)
        rng = np.random.default_rng(self.seed)
        absent = np.setdiff1d(
            rng.integers(0, c.n_features, 2 * UNTOUCHED_FEATURES), ufeat
        )[:UNTOUCHED_FEATURES]
        quiet = (absent[:, None] * nf + np.arange(nf)).reshape(-1)

        def padded(ids, like):
            out = np.zeros(like.size, like.dtype)
            out[: ids.size] = ids
            return out

        with self.spans.span("ffm.warmup"):
            before = np.asarray(self._gather_rows(
                self._params[2], rows.reshape(-1))).reshape(
                    rows.shape + (c.k,))
            quiet_before = self._rows_of(quiet)
            loss = self._fit(iter(self.pool[:1]))
            self.first_step = {
                "before": before, "loss": float(loss[0]),
                "rows": self._rows_of(uniq),
                "w": np.asarray(self._gather(
                    self._params[1], padded(ufeat, feats)))[: ufeat.size],
                "w0": float(self._params[0]),
                "rounds": self.rounds[-1],
                "quiet_rows": int(quiet.size),
                "quiet_same": bool(np.array_equal(self._rows_of(quiet),
                                                  quiet_before)),
                "absent_w_same": bool(np.all(np.asarray(self._gather(
                    self._params[1], padded(absent, feats)))[
                        : absent.size] == 0.0)),
            }
            self._fit(iter(self.pool[1:3]))

    def _stream(self, keep_going) -> dict:
        n = [0]

        def chunks():
            t0 = time.perf_counter()
            while keep_going(n[0], time.perf_counter() - t0):
                yield self.pool[n[0] % len(self.pool)]
                n[0] += 1

        t0 = time.perf_counter()
        with self.spans.span("ffm.fit_stream"):
            losses = self._fit(chunks())
        elapsed = time.perf_counter() - t0
        self.losses.append(losses)
        t = self.traffic
        rows = n[0] * t["rows_per_chunk"]
        slots = n[0] * t["rows_per_chunk"] * self.cfg.max_nnz
        taken = np.arange(n[0]) % len(self.pool)    # the chunks consumed
        counts = {name: values[taken]
                  for name, values in self.pool_counts.items()}
        distinct = float(counts["distinct_features"].sum())
        rounds = self.rounds[-1]
        return {"attempted": n[0],
                "failed": int(np.count_nonzero(~np.isfinite(losses))),
                "metrics": {"rows_per_s": rows / elapsed},
                "counters": {
                    "chunks": n[0], "rows": rows, "elapsed_s": elapsed,
                    "slots": slots, "distinct_features": distinct,
                    "distinct_share": 100.0 * distinct / max(slots, 1),
                    "remote_blocks": float(counts["remote_blocks"].sum()),
                    "owner_load_max_over_mean": float(
                        counts["owner_load"].mean()) if n[0] else None,
                    "exchange_rounds": rounds,
                    "exchange_rounds_per_chunk": rounds / max(n[0], 1)},
                "log": {"first_losses": [float(v) for v in losses[:3]],
                        "last_loss": float(losses[-1])}}

    def window(self, seconds: float) -> dict:
        """One ``fit_stream`` call whose generator yields pool chunks in
        order until ``seconds`` have elapsed."""
        return self._stream(lambda done, elapsed: elapsed < seconds)

    def slice(self) -> dict:
        """The traced slice: one ``fit_stream`` over ``trace_chunks``."""
        limit = self.traffic["trace_chunks"]
        return self._stream(lambda done, elapsed: done < limit)

    def check(self):
        """Against ``reference/ffm_sharded.py``, which knows of no owner:
        the first chunk's loss, the new bias, the new value and the
        UPDATE of every table row and linear weight it touched, gathered
        from the sharded table; the rows of features it lacks
        bit-identical; no loss of the run non-finite. The limits and
        what each is for are in the reference's docstring."""
        got = self.first_step
        feats, _fields, vals, y = self.pool[0]
        want = reference.step(got["before"], self._slot_rows(self.pool[0]),
                              feats, vals, y, self.cfg.learning_rate)
        R, A = reference.RTOL, reference.ATOL
        before, update = want["before"], want["update"]
        moved = got["rows"].astype(np.float64) - before
        w = got["w"].astype(np.float64)
        excess = {
            "row_value": _excess(moved - update,
                                 A + R * np.abs(before + update)),
            "row_update": _excess(moved - update, reference.ATOL_UPDATE
                                  + R * np.abs(update)),
            "w_value": _excess(w - want["w"], A + R * np.abs(want["w"])),
            "w_of_terms": _excess(w - want["w"], reference.W_TERMS_RTOL
                                  * want["w_terms"]),
            "w0": _excess(got["w0"] - want["w0"], A + R * abs(want["w0"])),
            "loss": _excess(got["loss"] - want["loss"],
                            R * abs(want["loss"])),
        }
        norm = float(np.linalg.norm(update))
        detail = {
            "loss": got["loss"], "reference_loss": want["loss"],
            "rows_checked": int(want["rows"].size),
            "features_checked": int(want["feats"].size),
            "excess": excess,
            "row_update_max_abs_err": float(np.abs(moved - update).max()),
            "row_update_rel_norm_err": float(
                np.linalg.norm(moved - update) / norm) if norm else None,
            "w0": got["w0"], "reference_w0": want["w0"],
            "first_chunk_exchange_rounds": got["rounds"],
            "quiet_rows": got["quiet_rows"],
            "quiet_same": got["quiet_same"],
            "absent_w_same": got["absent_w_same"],
            "losses_finite": bool(all(np.isfinite(v).all()
                                      for v in self.losses)),
        }
        detail["compared"] = {
            **{f"{name}_err_over_allowed": [v, 1.0]
               for name, v in excess.items()},
            "quiet_same": [int(bool(got["quiet_same"])), 1],
            "absent_w_same": [int(bool(got["absent_w_same"])), 1],
            "losses_finite": [int(detail["losses_finite"]), 1],
            "steps_at_least": [len(self.losses), 1]}
        ok = (all(v <= 1.0 for v in excess.values())
              and got["quiet_same"] and got["absent_w_same"]
              and detail["losses_finite"] and len(self.losses) > 0)
        return bool(ok), detail
