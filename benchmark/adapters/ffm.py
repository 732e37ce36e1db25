"""Drives ``FMTrainer.fit_stream()``: one call consumes minibatches until
the window's seconds have elapsed.

Only the trainer's public surface is used: the constructor, its ``mesh``
and ``fit_stream(batches, params=, batch_rows=, max_in_flight=)``. The
embedding table is made on the device from the seed in one jitted call
(``params_maker``) and handed to ``fit_stream(params=...)``:
``init_params()`` would draw a billion normals on the host in every run.

The step donates nothing, so the table it reads and the table it writes
are both live, and ``fit_stream`` keeps the table it was given until it
returns. The adapter therefore never holds a table across a call: the
parameters live in ``self._params`` only between calls (``_take``).
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import traffic as traffic_gen
from benchmark.reference import ffm as reference
from ytk_mp4j_tpu.models.fm import FMConfig, FMTrainer

RTOL = 1e-4     # f32 step against the f64 reference
ATOL = 1e-7     # updates of ~1e-6 on values of ~1e-2, summed in f32


def params_maker(config, sharding):
    """A jitted ``key -> (w0, w, table)`` that makes the initial
    parameters on the device, already in the placement the step takes
    (``fit_stream``'s own device_put is then a no-op and no second table
    is made). The table is drawn one field's block of rows at a time and
    written in place, so the program's temporaries are a block, not most
    of a table."""
    n_features, n_fields, k = (config["n_features"], config["n_fields"],
                               config["k"])
    scale = config["init_scale"]

    def make_params(key):
        def block(i, table):
            rows = scale * jax.random.normal(
                jax.random.fold_in(key, i), (n_features, k), jnp.float32)
            return lax.dynamic_update_slice(table, rows, (i * n_features, 0))
        table = lax.fori_loop(
            0, n_fields, block,
            jnp.zeros((n_features * n_fields, k), jnp.float32))
        return (jnp.zeros((), jnp.float32),
                jnp.zeros((n_features,), jnp.float32), table)

    return jax.jit(make_params, out_shardings=(sharding,) * 3)


class Adapter:
    def __init__(self, config, traffic, seed, devices, spans):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.spans = devices, spans
        self._params = None
        self.losses = []

    def setup(self):
        c, t = self.config, self.traffic
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
        make_params = params_maker(c, NamedSharding(self.trainer.mesh, P()))
        with self.spans.span("ffm.make_table"):
            self._params = make_params(jax.random.key(self.seed))
            jax.block_until_ready(self._params)
        self._gather = jax.jit(lambda table, rows: table[rows])

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
        return losses

    def _slot_rows(self, chunk):
        """Table row of every slot pair: v[feat_a, field_b] is row
        ``feat_a * n_fields + field_b``; [N, K, K]."""
        feats, fields = chunk[:2]
        return feats[:, :, None] * self.cfg.n_fields + fields[:, None, :]

    def warmup(self):
        """The first chunk alone (compiles the step; its inputs and
        outputs are fetched for the check), then two more through the
        pipelined loop. The window starts from the table this leaves."""
        feats = self.pool[0][0]
        rows = self._slot_rows(self.pool[0])

        def padded(unique, like):
            # one gather program a table, whatever the seed's unique count
            out = np.zeros(like.size, like.dtype)
            out[: unique.size] = unique
            return out.reshape(like.shape)

        with self.spans.span("ffm.warmup"):
            before = np.asarray(self._gather(self._params[2], rows))
            loss = self._fit(iter(self.pool[:1]))
            uniq, ufeat = np.unique(rows), np.unique(feats)
            after_rows = np.asarray(self._gather(
                self._params[2], padded(uniq, rows))).reshape(
                    -1, self.cfg.k)[: uniq.size]
            after_w = np.asarray(self._gather(
                self._params[1], padded(ufeat, feats))).reshape(
                    -1)[: ufeat.size]
            after_w0 = float(self._params[0])
            self.first_step = (before, float(loss[0]), after_rows, after_w,
                               after_w0)
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
                             "elapsed_s": elapsed},
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
        """Against ``reference/ffm.py``: the first chunk's loss, the new
        bias and the new values of every table row and linear weight it
        touched equal one float64 SGD step on the rows gathered from the
        initial table; no loss of the run is non-finite."""
        before, loss, after_rows, after_w, after_w0 = self.first_step
        feats, _fields, vals, y = self.pool[0]
        rows = self._slot_rows(self.pool[0])
        # the linear weights and the bias start at zero
        want = reference.step(before, np.zeros(feats.shape), 0.0, rows,
                              feats, vals, y, self.cfg.learning_rate)
        want_loss, want_w0, uniq, want_rows, ufeat, want_w = want
        detail = {
            "loss": loss, "reference_loss": want_loss,
            "rows_checked": int(uniq.size),
            "row_max_abs_err": float(np.abs(after_rows - want_rows).max()),
            "w_max_abs_err": float(np.abs(after_w - want_w).max()),
            "w0": after_w0, "reference_w0": want_w0,
            "losses_finite": bool(all(np.isfinite(v).all()
                                      for v in self.losses)),
        }

        def excess(got, want, atol):
            """Largest error as a share of what ``allclose`` allows."""
            want = np.asarray(want, np.float64)
            return float(np.max(np.abs(np.asarray(got, np.float64) - want)
                                / (atol + RTOL * np.abs(want)), initial=0.0))

        detail["compared"] = {
            "loss_err_over_allowed": [excess(loss, want_loss, 0.0), 1.0],
            "row_err_over_allowed": [excess(after_rows, want_rows, ATOL),
                                     1.0],
            "w_err_over_allowed": [excess(after_w, want_w, ATOL), 1.0],
            "w0_err_over_allowed": [excess(after_w0, want_w0, ATOL), 1.0],
            "losses_finite": [int(detail["losses_finite"]), 1],
            "steps_at_least": [len(self.losses), 1]}
        ok = (np.isclose(loss, want_loss, rtol=RTOL, atol=0)
              and np.allclose(after_rows, want_rows, rtol=RTOL, atol=ATOL)
              and np.allclose(after_w, want_w, rtol=RTOL, atol=ATOL)
              and np.isclose(after_w0, want_w0, rtol=RTOL, atol=ATOL)
              and detail["losses_finite"] and len(self.losses) > 0)
        return bool(ok), detail
