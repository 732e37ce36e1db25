"""Drives ``FMTrainer.fit_stream()`` under libffm's rule
(``FMConfig(optimizer="adagrad")``): one call consumes minibatches until
the window's seconds have elapsed, as ``adapters/ffm.py`` does for SGD.

Only the trainer's public surface is used: the constructor, its ``mesh``,
``fit_stream(batches, params=, batch_rows=, max_in_flight=, opt_state=)``
and ``opt_state_``, the accumulators the last call left. The table is
drawn on the device from the seed (uniform in [0, 1/sqrt(k)), libffm's
start; linear weights and bias 0); the accumulators of the first call are
the trainer's fresh ones (``adagrad_init`` beside every parameter) and
every later call is handed those of the call before. Values are scaled to
unit length a row (libffm's instance-wise normalisation, done on reading
there) before ``fit_stream`` sees them.

The step's table holds parameters and accumulators (6.44 GB); the public
parameters (2.62 GB) and accumulators (2.62 GB) are alive beside it only
while they are converted, so the adapter never holds either across a
call: the parameters live in ``self._params`` between calls (``_take``),
the accumulators in the trainer's ``opt_state_``, which ``fit_stream``
lets go of as soon as it has them in its blocks.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import traffic as traffic_gen
from benchmark.reference import ffm_adagrad as reference
from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

# What the check holds the first chunk's step to, against the float64
# reference on the same rows. A parameter's UPDATE (after - before) and an
# accumulator's INCREMENT (G - adagrad_init) are compared, not the values:
# beside a vector entry of 0.25 or an accumulator of 1.0 an update of 1e-3
# or an increment of 4e-5 would pass any tolerance on the value.
#
# rtol: g is an f32 sum of up to a few thousand terms (the hottest feature
# of a 2,048-row chunk) of products that crossed the MXU in three bf16
# pieces; measured on the chip at most 3e-5 of the update (PERF.md §6).
# atol: the stored value rounds to f32. An update is the difference of two
# values below 0.5 + 0.2 (half an ulp each: 6e-8), an increment that of
# two values near 1 (1.2e-7 an ulp), more for the few hot parameters whose
# G has grown past 2.
RTOL = 2e-4
LOSS_RTOL = 1e-4    # the accepted FFM cell's: an f32 mean of 2,048 losses
ATOL_UPDATE = 2e-7
ATOL_ACC = 5e-7
UNTOUCHED_FEATURES = 4096     # features the chunk lacks, every row of theirs


def params_maker(config, sharding):
    """A jitted ``key -> (w0, w, table)``: the table uniform in
    [0, init_uniform_high), drawn a field's block of rows at a time and
    written in place (temporaries: a block), already placed as the step
    takes it."""
    n_features, n_fields, k = (config["n_features"], config["n_fields"],
                               config["k"])
    high = config["init_uniform_high"]

    def make_params(key):
        def block(i, table):
            rows = jax.random.uniform(
                jax.random.fold_in(key, i), (n_features, k), jnp.float32,
                0.0, high)
            return lax.dynamic_update_slice(table, rows, (i * n_features, 0))
        table = lax.fori_loop(
            0, n_fields, block,
            jnp.zeros((n_features * n_fields, k), jnp.float32))
        return (jnp.zeros((), jnp.float32),
                jnp.zeros((n_features,), jnp.float32), table)

    return jax.jit(make_params, out_shardings=(sharding,) * 3)


def _excess(got, want, rtol, atol) -> float:
    """Largest error as a share of what is allowed: <= 1 passes."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / (atol + rtol * np.abs(want)),
                        initial=0.0))


class Adapter:
    def __init__(self, config, traffic, seed, devices, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.spans = devices, spans
        self._params = None
        self.losses = []

    def setup(self):
        c, t = self.config, self.traffic
        # first thing: a program whose FMConfig knows no optimizer fails
        # here, in seconds, before any pool or table is made
        self.cfg = FMConfig(
            model=c["model"], n_features=c["n_features"],
            n_fields=c["n_fields"], k=c["k"], max_nnz=c["max_nnz"],
            loss=c["loss"], learning_rate=c["learning_rate"], l2=c["l2"],
            optimizer=c["optimizer"], adagrad_init=c["adagrad_init"])
        self.trainer = FMTrainer(
            self.cfg, n_devices=len(self.devices),
            sparse_grads=c["sparse_grads"],
            table_sharding=c["table_sharding"])
        with self.spans.span("ffm.make_pool"):
            pool = traffic_gen.zipf_chunk_pool(
                self.seed, c["n_features"], c["n_fields"],
                t["rows_per_chunk"], t["pool_chunks"], t["zipf_exponent"],
                t["positive_rate"])
            if c["instance_norm"]:
                pool = [(f, fl, (v / np.sqrt(np.sum(v * v, axis=1,
                                                    keepdims=True))
                                 ).astype(np.float32), y)
                        for f, fl, v, y in pool]
            self.pool = pool
            self.slots = t["rows_per_chunk"] * c["max_nnz"]
            self.distinct = float(np.mean(
                [np.unique(chunk[0]).size for chunk in pool]))
        make_params = params_maker(c, NamedSharding(self.trainer.mesh, P()))
        with self.spans.span("ffm.make_table"):
            self._params = make_params(jax.random.key(self.seed))
            jax.block_until_ready(self._params)
        self._gather = jax.jit(lambda table, rows: table[rows])

    def _take(self):
        params, self._params = self._params, None
        return params

    def _fit(self, chunks):
        """``fit_stream`` over ``chunks`` from the current parameters and
        the accumulators the call before left (none: fresh ones);
        returns the per-chunk losses."""
        t = self.traffic
        self._params, losses = self.trainer.fit_stream(
            chunks, params=self._take(), batch_rows=t["rows_per_chunk"],
            max_in_flight=t["max_in_flight"],
            opt_state=self.trainer.opt_state_)
        return losses

    def _rows_of(self, table, rows, width):
        """``table[rows]`` on the host, for a sorted set of rows of any
        length: one gather program a table (the set padded to the
        chunk's slot pairs)."""
        n = self.slots * self.cfg.max_nnz
        out = []
        for i in range(0, rows.size, n):
            part = np.zeros(n, rows.dtype)
            part[: rows[i:i + n].size] = rows[i:i + n]
            out.append(np.asarray(self._gather(table, part)).reshape(
                n, *width)[: rows[i:i + n].size])
        return np.concatenate(out)

    def warmup(self):
        """The first chunk alone, from fresh accumulators (compiles the
        step and both conversions; what it read and wrote is fetched for
        the check), then two more with the state handed over (the
        conversion that takes accumulators in). The window starts from
        what this leaves."""
        c, nf = self.cfg, self.cfg.n_fields
        feats, fields = self.pool[0][:2]
        rows = feats[:, :, None] * nf + fields[:, None, :]
        reached = np.unique(rows[:, ~np.eye(c.max_nnz, dtype=bool)])
        ufeat = np.unique(feats)
        # never reached: a present feature's vector against its own field
        # (one feature a field), and every row of features the chunk lacks
        rng = np.random.default_rng(self.seed)
        absent = np.setdiff1d(
            rng.integers(0, c.n_features, 2 * UNTOUCHED_FEATURES), ufeat
        )[:UNTOUCHED_FEATURES]
        quiet = np.setdiff1d(np.concatenate(
            [np.unique(feats * nf + fields),
             (absent[:, None] * nf + np.arange(nf)).reshape(-1)]), reached)
        k = (c.k,)
        with self.spans.span("ffm.warmup"):
            table = self._params[2]
            before = self._rows_of(table, rows.reshape(-1), k).reshape(
                rows.shape + k)
            quiet_before = self._rows_of(table, quiet, k)
            del table
            loss = self._fit(iter(self.pool[:1]))
            w0, w, V = self._params
            G0, Gw, GV = self.trainer.opt_state_
            self.first_step = {
                "before": before, "loss": float(loss[0]),
                "rows": reached, "V": self._rows_of(V, reached, k),
                "GV": self._rows_of(GV, reached, k),
                "feats": ufeat, "w": self._rows_of(w, ufeat, ()),
                "Gw": self._rows_of(Gw, ufeat, ()),
                "w0": float(w0), "G0": float(G0),
                "quiet_same": bool(np.array_equal(
                    self._rows_of(V, quiet, k), quiet_before)),
                "quiet_acc_fresh": bool(np.all(
                    self._rows_of(GV, quiet, k)
                    == np.float32(c.adagrad_init))),
                "absent_w_same": bool(
                    np.all(self._rows_of(w, absent, ()) == 0.0)
                    and np.all(self._rows_of(Gw, absent, ())
                               == np.float32(c.adagrad_init))),
                "quiet_rows": int(quiet.size),
            }
            del w0, w, V, G0, Gw, GV
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
        rows = n[0] * self.traffic["rows_per_chunk"]
        return {"attempted": n[0],
                "failed": int(np.count_nonzero(~np.isfinite(losses))),
                "metrics": {"rows_per_s": rows / elapsed},
                "counters": {"chunks": n[0], "rows": rows,
                             "elapsed_s": elapsed, "slots": self.slots,
                             "distinct_features": self.distinct,
                             "distinct_share": (100.0 * self.distinct
                                                / self.slots)},
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
        """Against ``reference/ffm_adagrad.py``: the first chunk's loss;
        the bias's update and its accumulator's increment; for every
        table row and linear weight the chunk reached its update and its
        accumulator's increment; what the chunk did not reach
        bit-identical with accumulators exactly ``adagrad_init``; no loss
        of the run non-finite."""
        c, got = self.cfg, self.first_step
        feats, _fields, vals, y = self.pool[0]
        rows = feats[:, :, None] * c.n_fields + _fields[:, None, :]
        init = c.adagrad_init
        # linear weights and bias start at 0, every accumulator at init
        zeros = np.zeros(feats.shape)
        want_loss, (w0, G0), (urows, V, GV), (ufeat, w, Gw) = reference.step(
            got["before"], np.full(got["before"].shape, init), zeros,
            zeros + init, 0.0, init, rows, feats, vals, y,
            np.ones(len(y)), c.learning_rate, c.l2)
        same_sets = (np.array_equal(urows, got["rows"])
                     and np.array_equal(ufeat, got["feats"]))
        # the rows reached are among the rows gathered (which hold the
        # vectors against the features' own fields too)
        gathered, first = np.unique(rows.reshape(-1), return_index=True)
        start = got["before"].reshape(-1, c.k)[first][
            np.searchsorted(gathered, urows)].astype(np.float64)
        excess = {
            "row_update": _excess(got["V"].astype(np.float64) - start,
                                  V - start, RTOL, ATOL_UPDATE),
            "row_acc": _excess(got["GV"].astype(np.float64) - init,
                               GV - init, RTOL, ATOL_ACC),
            "w_update": _excess(got["w"], w, RTOL, ATOL_UPDATE),
            "w_acc": _excess(got["Gw"].astype(np.float64) - init,
                             Gw - init, RTOL, ATOL_ACC),
            "w0_update": _excess(got["w0"], w0, RTOL, ATOL_UPDATE),
            "w0_acc": _excess(got["G0"] - init, G0 - init, RTOL, ATOL_ACC),
        } if same_sets else {}
        detail = {
            "loss": got["loss"], "reference_loss": want_loss,
            "rows_checked": int(urows.size), "same_sets": bool(same_sets),
            "excess": excess,
            "quiet_rows": got["quiet_rows"],
            "quiet_same": got["quiet_same"],
            "quiet_acc_fresh": got["quiet_acc_fresh"],
            "absent_w_same": got["absent_w_same"],
            "losses_finite": bool(all(np.isfinite(v).all()
                                      for v in self.losses)),
        }
        detail["compared"] = {
            "same_sets": [int(same_sets), 1],
            "loss_err_over_allowed": [
                _excess(got["loss"], want_loss, LOSS_RTOL, 0.0), 1.0],
            **{f"{name}_err_over_allowed": [v, 1.0]
               for name, v in excess.items()},
            "quiet_same": [int(bool(got["quiet_same"])), 1],
            "quiet_acc_fresh": [int(bool(got["quiet_acc_fresh"])), 1],
            "absent_w_same": [int(bool(got["absent_w_same"])), 1],
            "losses_finite": [int(detail["losses_finite"]), 1],
            "steps_at_least": [len(self.losses), 1]}
        ok = (same_sets and np.isclose(got["loss"], want_loss,
                                       rtol=LOSS_RTOL, atol=0)
              and all(v <= 1.0 for v in excess.values())
              and got["quiet_same"] and got["quiet_acc_fresh"]
              and got["absent_w_same"]
              and detail["losses_finite"] and len(self.losses) > 0)
        return bool(ok), detail
