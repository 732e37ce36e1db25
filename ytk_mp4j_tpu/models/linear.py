"""TPU-native distributed linear models (linear & logistic regression).

ytk-mp4j's consumer ytk-learn ships a "linear" model family trained by
data-parallel gradient descent: each worker computes gradients on its
shard and the gradient vector is ALLREDUCED every step (the same pattern
as the GBDT histogram allreduce, SURVEY.md section 1 — gradient
aggregation is the library's reason to exist).

TPU-first rebuild: the whole optimization step — forward, loss, grad,
``lax.psum`` over the mesh axis, optimizer update — is ONE jitted
``shard_map`` program. The gradient allreduce that the reference performs
with Kryo-socket recursive halving (SURVEY.md section 3b) is a single XLA
ICI collective; parameters stay replicated, data stays sharded.

Losses: ``squared`` (regression), ``logistic`` (binary classification,
labels in {0, 1}), and ``softmax`` (ytk-learn's multiclass_linear
family: w becomes [F, C], labels are int class ids); L2 as a penalty
gradient added before the momentum update (coupled, classic
SGD-with-weight-penalty; the reported loss is the data term only), L1
via a proximal shrink after the step (so momentum still sees a smooth
objective).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models._base import (DataParallelTrainer, EarlyStopper,
                                       per_example_loss,
                                       stage_softmax_labels)

LOSSES = ("squared", "logistic", "softmax")


@dataclass(frozen=True)
class LinearConfig:
    n_features: int
    loss: str = "squared"
    n_classes: int = 2          # used by loss="softmax" only
    learning_rate: float = 0.1
    l1: float = 0.0
    l2: float = 0.0
    momentum: float = 0.0

    def __post_init__(self):
        if self.loss not in LOSSES:
            raise Mp4jError(f"loss must be one of {LOSSES}, got {self.loss!r}")
        if self.loss == "softmax" and self.n_classes < 2:
            raise Mp4jError("softmax needs n_classes >= 2")


def _mean_loss_grad(params, x, y, sample_w, cfg: LinearConfig, axis_name):
    """Global-mean gradient of the (unregularized) loss.

    The psum'd (sum_grad, sum_weight) pair turns per-shard sums into the
    exact global mean — weighting also neutralizes padding rows (weight
    0), so sharded and single-device runs match bitwise up to reduction
    order.

    Params arrive replicated (``P()``); they are cast device-varying
    with ``lax.pcast`` before differentiation so the gradient stays a
    PER-SHARD quantity and the cross-shard sum is the EXPLICIT ``psum``
    below. (Without this, shard_map's varying-axis autodiff inserts the
    psum itself — the transpose of replication — and an explicit psum on
    top would multiply gradients by the shard count.)
    """
    w, b = params
    if axis_name is not None:
        w = lax.pcast(w, axis_name, to="varying")
        b = lax.pcast(b, axis_name, to="varying")

    def shard_sums(w, b):
        z = x @ w + b
        return jnp.sum(per_example_loss(z, y, cfg.loss) * sample_w)

    sum_loss, grads = jax.value_and_grad(
        lambda p: shard_sums(*p))((w, b))
    cnt = jnp.sum(sample_w)
    if axis_name is not None:
        sum_loss = lax.psum(sum_loss, axis_name)
        grads = jax.tree_util.tree_map(
            lambda g: lax.psum(g, axis_name), grads)  # THE gradient allreduce
        cnt = lax.psum(cnt, axis_name)
    denom = jnp.maximum(cnt, 1.0)
    mean_grads = jax.tree_util.tree_map(lambda g: g / denom, grads)
    return sum_loss / denom, mean_grads


def train_step_shard(params, vel, x, y, sample_w, cfg: LinearConfig,
                     axis_name=None):
    """One optimization step on this shard. Returns (params, vel, loss)."""
    loss, (gw, gb) = _mean_loss_grad(params, x, y, sample_w, cfg, axis_name)
    w, b = params
    gw = gw + cfg.l2 * w                      # L2 penalty (not on bias)
    vw, vb = vel
    vw = cfg.momentum * vw + gw
    vb = cfg.momentum * vb + gb
    w = w - cfg.learning_rate * vw
    b = b - cfg.learning_rate * vb
    if cfg.l1 > 0.0:
        # proximal shrink keeps the objective smooth for momentum
        shrink = cfg.learning_rate * cfg.l1
        w = jnp.sign(w) * jnp.maximum(jnp.abs(w) - shrink, 0.0)
    return (w, b), (vw, vb), loss


def predict(params, x, cfg: LinearConfig):
    w, b = params
    z = x @ w + b
    if cfg.loss == "logistic":
        return jax.nn.sigmoid(z)
    if cfg.loss == "softmax":
        return jax.nn.softmax(z, axis=-1)
    return z


class LinearTrainer(DataParallelTrainer):
    """Data-parallel linear/logistic regression over a mesh.

    The per-step program is one jitted ``shard_map``: data sharded over
    the mesh axis (or axes, for a hierarchical inter x intra mesh),
    parameters and optimizer state replicated, gradients psum'd.
    """

    def __init__(self, cfg: LinearConfig, mesh=None, n_devices=None):
        super().__init__(mesh=mesh, n_devices=n_devices)
        self.cfg = cfg
        self._step = None
        self._eval_fn = None
        self.eval_history_: list[float] = []

    def init_params(self):
        if self.cfg.loss == "softmax":
            # w [F, C], b [C]: ytk-learn's multiclass_linear family
            return (jnp.zeros((self.cfg.n_features, self.cfg.n_classes),
                              jnp.float32),
                    jnp.zeros((self.cfg.n_classes,), jnp.float32))
        return (jnp.zeros((self.cfg.n_features,), jnp.float32),
                jnp.zeros((), jnp.float32))

    def _build_step(self):
        cfg = self.cfg
        axes = self.axes
        dspec = P(axes)

        @partial(jax.shard_map, mesh=self.mesh,
                 in_specs=(P(), P(), dspec, dspec, dspec),
                 out_specs=(P(), P(), P()))
        def step(params, vel, x, y, sw):
            return train_step_shard(params, vel, x[0], y[0], sw[0], cfg, axes)

        return jax.jit(step)

    def shard_data(self, x: np.ndarray, y: np.ndarray,
                   sample_weight=None):
        """Pad + reshape to [n_shards, N/shard, ...]; padding rows carry
        sample weight 0 so results match unsharded runs for any N.
        ``sample_weight`` ([N], optional — ytk-learn's instance
        weights) scales each example's loss/gradient (the step
        normalizes by the weight sum: integer weights == row
        duplication)."""
        x = np.asarray(x, np.float32)
        y = self._stage_labels(y)
        if x.ndim != 2 or x.shape[1] != self.cfg.n_features:
            raise Mp4jError(
                f"x must be [N, {self.cfg.n_features}], got {x.shape}")
        N = x.shape[0]
        (x, y), per, sw = self._pad_rows([x, y])
        sw[:N] *= self._stage_weights(sample_weight, N)
        return (self._put_sharded(x, per), self._put_sharded(y, per),
                self._put_sharded(sw, per))

    def fit(self, x: np.ndarray, y: np.ndarray, n_steps: int = 100,
            params=None, eval_set=None,
            early_stopping_rounds: int | None = None,
            sample_weight=None):
        """Run ``n_steps`` full-batch steps; returns (params, losses).

        ``eval_set=(x_va, y_va)`` tracks held-out loss per step (history
        in ``self.eval_history_``); ``early_stopping_rounds=k`` stops
        after k non-improving steps and returns the best round's
        params; ``sample_weight`` weights examples (see
        :meth:`shard_data`).
        """
        if early_stopping_rounds is not None and eval_set is None:
            raise Mp4jError("early_stopping_rounds requires an eval_set")
        if self._step is None:
            self._step = self._build_step()
        dx, dy, dsw = self.shard_data(x, y, sample_weight=sample_weight)
        if params is None:
            params = self.init_params()
        # committed up front: an uncommitted first call would compile
        # the step twice (see DataParallelTrainer._place_replicated)
        params = self._place_replicated(params)
        vel = jax.tree_util.tree_map(jnp.zeros_like, params)
        va = None
        if eval_set is not None:
            x_va = np.asarray(eval_set[0], np.float32)
            y_va = self._stage_labels(eval_set[1])
            if x_va.ndim != 2 or x_va.shape[1] != self.cfg.n_features:
                raise Mp4jError(
                    f"eval x must be [N, {self.cfg.n_features}], "
                    f"got {x_va.shape}")
            if y_va.shape != (x_va.shape[0],):
                raise Mp4jError(
                    f"eval y must be [{x_va.shape[0]}], got {y_va.shape}")
            va = (jnp.asarray(x_va), jnp.asarray(y_va))
        stopper = EarlyStopper(early_stopping_rounds)
        self.eval_history_ = stopper.history
        losses = []
        for i in range(n_steps):
            params, vel, loss = self._step(params, vel, dx, dy, dsw)
            # Synchronize each step: on hosts with fewer cores than mesh
            # devices, letting hundreds of small multi-collective programs
            # queue up can starve XLA's CPU collective rendezvous (its
            # device threads block 40s then abort). One program in flight
            # at a time costs nothing here (steps are data-dependent
            # anyway) and keeps the thread demand bounded.
            loss = jax.block_until_ready(loss)
            losses.append(loss)
            if va is not None and stopper.update(
                    self._eval_loss(params, va), i, state=(params, vel)):
                if stopper.best_state is not None:
                    params, vel = stopper.best_state
                    losses = losses[:stopper.best_round + 1]
                break
        return params, np.asarray(jax.device_get(losses))

    def fit_stream(self, batches, params=None,
                   batch_rows: int | None = None,
                   max_in_flight: int = 2):
        """Chunked (out-of-core) training: one optimizer step per
        ``(x, y)`` chunk (or ``(x, y, w)`` with per-chunk instance
        weights) — ytk-learn's linear family trains from the
        same streamed libsvm text as FFM
        (``utils.libsvm.read_libsvm`` + ``utils.libsvm.dense_chunks``
        adapts it to the dense [N, F] this model consumes). Chunks pad
        to ``batch_rows`` (default: first chunk, rounded up to the
        shard count) with zero-weight rows so ONE jitted program
        serves the stream; momentum state threads across chunks; the
        pipeline double-buffers exactly like
        :meth:`FMTrainer.fit_stream` (``max_in_flight=0``
        serializes). Feeding the full dataset as a single chunk E
        times is numerically identical to ``fit(n_steps=E)`` (tested).
        Returns (params, per-chunk losses)."""
        if self._step is None:
            self._step = self._build_step()
        if params is None:
            params = self.init_params()
        params = self._place_replicated(params)
        state = [params, jax.tree_util.tree_map(jnp.zeros_like, params)]

        def dispatch(staged):
            # the throttle inside _stream_fit also bounds the queued
            # multi-collective programs — see the sync note in fit()
            state[0], state[1], loss = self._step(state[0], state[1],
                                                  *staged)
            return loss

        losses = self._stream_fit(batches, self._stage_stream_chunk,
                                  dispatch, batch_rows, max_in_flight)
        return state[0], losses

    def _stage_stream_chunk(self, chunk, batch_rows: int | None):
        """Host half of one stream step: validate, pad to
        ``batch_rows`` (resolving it from the first chunk), start the
        async device placement."""
        x, y = chunk[:2]
        weights = chunk[2] if len(chunk) > 2 else None
        x = np.asarray(x, np.float32)
        y = self._stage_labels(y)
        if x.ndim != 2 or x.shape[1] != self.cfg.n_features:
            raise Mp4jError(
                f"x must be [N, {self.cfg.n_features}], got {x.shape}")
        if batch_rows is None:
            batch_rows = -(-x.shape[0] // self.n_shards) * self.n_shards
        N = x.shape[0]
        (x, y), sw, per = self._pad_stream_rows([x, y], batch_rows)
        sw[:N] *= self._stage_weights(weights, N)
        staged = (self._put_sharded(x, per), self._put_sharded(y, per),
                  self._put_sharded(sw, per))
        return staged, batch_rows

    def _stage_labels(self, y) -> np.ndarray:
        """Labels must be a flat [N] vector — a column-vector y would
        broadcast through the loss to an [N, N] matrix and train
        silently on garbage. softmax labels are additionally int32
        class ids validated in range (stage_softmax_labels, shared
        with the GBDT softmax path)."""
        y = np.asarray(y)
        if y.ndim != 1:
            raise Mp4jError(f"y must be 1-D [N], got shape {y.shape}")
        if self.cfg.loss != "softmax":
            return y.astype(np.float32)
        return stage_softmax_labels(y, self.cfg.n_classes)

    def _eval_loss(self, params, va) -> float:
        if self._eval_fn is None:
            cfg = self.cfg

            @jax.jit
            def run(params, x, y):
                w, b = params
                return jnp.mean(per_example_loss(x @ w + b, y, cfg.loss))

            self._eval_fn = run
        # params may span non-addressable devices on multi-process
        # meshes; a plain local jit cannot consume those directly
        return float(self._eval_fn(self._local_values(params), *va))

    def predict(self, params, x: np.ndarray) -> np.ndarray:
        x = jnp.asarray(np.asarray(x, np.float32))
        return np.asarray(predict(params, x, self.cfg))


# ----------------------------------------------------------------------
# serve adapter (ISSUE 19): the pull-mode sharded entry point
# ----------------------------------------------------------------------
class LinearServable:
    """Row-pull serve adapter for a trained linear model.

    ``kind="pull"``: the serve dispatcher shards the weight table by
    ``row_id % size`` across the job's ranks and the frontend pulls
    only the rows a batch touches over the columnar map plane —
    mirroring the owner-routed row fetch of the FFM AOT
    ``sharded_serve`` program on the host substrate. A row here is
    one feature's weight(s): width 1, or ``n_classes`` for softmax.
    Scoring is per example (never across the batch), so batched and
    sequential serve predictions are bitwise identical by
    construction.
    """

    kind = "pull"
    family = "linear"

    def __init__(self, params, cfg: LinearConfig):
        w, b = params
        self.cfg = cfg
        w = np.asarray(jax.device_get(w), np.float32)
        self._w = w if w.ndim == 2 else w[:, None]     # [D, width]
        self._b = np.atleast_1d(
            np.asarray(jax.device_get(b), np.float32))
        self.n_rows = self._w.shape[0]
        self.row_width = self._w.shape[1]
        self.resp_width = (cfg.n_classes if cfg.loss == "softmax"
                          else 1)

    def row_ids(self, req) -> np.ndarray:
        """Unique table rows one request touches (active slots only —
        a zero-valued slot contributes nothing, so its row is never
        pulled)."""
        ids, _fields, vals = req
        return np.unique(np.asarray(ids, np.int64)[
            np.asarray(vals, np.float32) != 0])

    def rows(self, ids) -> np.ndarray:
        """Float64 row vectors for the pull plane (the wire operand of
        ``allreduce_map`` is DOUBLE)."""
        return self._w[np.asarray(ids, np.int64)].astype(np.float64)

    def predict_sharded(self, reqs, rowmap) -> list:
        """Score a batch from pulled rows; one float64 vector per
        request. A row missing from ``rowmap`` scores as zeros — the
        degraded-but-deliverable contract the dispatcher's status byte
        reports."""
        out = []
        zero = np.zeros(self.row_width, np.float32)
        for ids, _fields, vals in reqs:
            ids = np.asarray(ids, np.int64)
            vals = np.asarray(vals, np.float32)
            z = self._b.astype(np.float32).copy()
            if self.cfg.loss != "softmax":
                z = z[:1].copy()
            for a in range(ids.shape[0]):
                if vals[a] == 0:
                    continue
                row = rowmap.get(int(ids[a]))
                row = zero if row is None else row.astype(np.float32)
                z += row * vals[a]
            out.append(_link(z, self.cfg.loss))
        return out


def _link(z: np.ndarray, loss: str) -> np.ndarray:
    """The prediction link on a host margin vector (numpy mirror of
    :func:`predict`'s heads, overflow-safe)."""
    z = np.asarray(z, np.float32)
    if loss == "logistic":
        p = np.empty_like(z, np.float64)
        pos = z >= 0
        p[pos] = 1.0 / (1.0 + np.exp(-z[pos].astype(np.float64)))
        e = np.exp(z[~pos].astype(np.float64))
        p[~pos] = e / (1.0 + e)
        return p
    if loss == "softmax":
        s = z.astype(np.float64) - z.max()
        e = np.exp(s)
        return e / e.sum()
    return z.astype(np.float64)


def servable(params, cfg: LinearConfig) -> LinearServable:
    """The serve plane's per-family entry point (ISSUE 19)."""
    return LinearServable(params, cfg)
