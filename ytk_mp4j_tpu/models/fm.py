"""TPU-native distributed factorization machines (FM and field-aware FFM).

ytk-mp4j's consumer ytk-learn ships FM and FFM model families whose
training loop allreduces EMBEDDING GRADIENTS every step — and because a
mini-batch touches only a sparse subset of the feature vocabulary, the
reference ships them as a sparse ``Map<String, Float[]>`` over the Kryo
socket path ("FFM gradient allreduce", BASELINE.json configs[4];
SURVEY.md section 3c).

TPU-first rebuild. Instances are padded to ``max_nnz`` static slots
(feature id / field id / value / mask), the whole step is one jitted
``shard_map`` program, and the gradient allreduce is:

- **dense mode** (default): the full embedding-table gradient rides one
  ``lax.psum`` — bandwidth ~|V| but maximally MXU/HBM friendly; right
  whenever the vocabulary fits comfortably on-chip.
- **sparse mode** (``sparse_grads=True``): per-slot gradients ride as
  static-shape ``(feature, grad_block)`` buffers — ONE all_gather each,
  then the map plane's merge (:func:`_merge_slots`:
  ``ops/sparse.sort_by_key`` + ``segment_reduce_sorted`` pack the slots
  into distinct ``(feature, summed grad_block)`` pairs, the
  device-native analogue of the reference's key-wise map merge), then a
  walk over the merged list's LIVE PREFIX a tile at a time
  (``ops/sparse.fold_live_tiles``) that touches the table once a
  distinct feature. All three sparse steps share that shape and differ
  in the walk's body: plain SGD scatter-ADDS a tile's summed gradients
  (:func:`train_step_sparse`; the sharded step the same on the owner's
  side), AdaGrad (``optimizer="adagrad"``, libffm's rule, which squares
  a feature's SUMMED gradient) gathers the tile's blocks, applies the
  rule and SETS them. SGD's update is linear in the gradient, so one
  scatter-add of every slot would merge duplicates natively; the SGD
  step merges all the same, because the serial unit charges by the
  descriptor and a click log's chunk holds each feature two or three
  times (42.5% distinct at Zipf 1.1: 7.00 ms for 79,872 slots against
  2.5 + 2.9 for the merge and 34,000 features, TPU v5 lite, PERF.md
  section 6, PR 39; the two forms meet at 61% distinct).
  Bandwidth ~nnz instead of ~|V|: the TPU translation of the
  reference's sparse map path. The replicated step holds the table by
  FEATURE, ``[n_features, block]`` with a feature's ``n_fields``
  vectors side by side in a row of whole 128-lane words, and touches
  them as one block: a (sample, feature) is one gather and one scatter
  descriptor, where a row a slot PAIR would be ``max_nnz`` times as
  many. The serial unit charges by the descriptor, not by its bytes
  (TPU v5 lite, PR 27, 79,872 descriptors: scatter-add 89.3 ns at
  1 KB against 87.0 ns at 16 B, gather 13.0 against 17.3). The
  feature's linear weight rides in the block's last column, so the
  step makes that one gather a slot and that one scatter-add a distinct
  feature and touches nothing else of size ``n_features``. Under
  AdaGrad every parameter's accumulator rides in the same block, in the
  block's second half (157 and 157 floats in a row of 384), so the
  state costs no descriptor of its own. The step donates that table and
  updates it in place;
  ``fit`` / ``fit_stream`` convert the public ``(w0, w, V)`` (and the
  accumulators, in the same shapes) once on the way in and once on the
  way out.
- **sharded table** (``table_sharding="sharded"``, on the sparse path):
  the same table by feature, cut over the mesh by contiguous ranges of
  features, member m holding the blocks of features ``[m * B, (m + 1) *
  B)`` and nobody the whole. A step fetches the blocks of the DISTINCT
  features its rows hold from their owners and returns their summed
  gradients the same way (:func:`train_step_sparse_sharded`), both
  through ``ops/collectives.all_to_all``; an owner gathers and
  scatter-adds what it owns and a chunk touches, not every member's
  slots. This is the form for a vocabulary whose table no chip can hold
  (configs[4] hashed to 2^25: 34.4 GB by feature).

Model scores (order-2, sigmoid/logloss for classification):

- FM:  ``w0 + sum_i w_i x_i + sum_{a<b} <v_a, v_b> x_a x_b`` with the
  O(K k) sum-of-squares identity.
- FFM: ``v`` is per (feature, field): ``sum_{a<b} <v_{a, field_b},
  v_{b, field_a}> x_a x_b`` over K^2 slot pairs (K = max_nnz, static).

Scoring a file (``FMTrainer.predict`` on a replicated table) reads the
same table by feature, parameters only (``FMTrainer.enter_model``
converts the public params once a model): :func:`score_rows` under
``shard_map``, rows sharded and no collective, a tile of rows at a time,
over instances that cross a piece of rows at a time (``_array_cuts``)
and are scored as they cross, each piece let go after its turn.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ytk_mp4j_tpu.exceptions import Mp4jError
from ytk_mp4j_tpu.models._base import (DataParallelTrainer,
                                       EarlyStopper, per_example_loss)
from ytk_mp4j_tpu.obs import spans
from ytk_mp4j_tpu.operators import Operators
from ytk_mp4j_tpu.ops import collectives
from ytk_mp4j_tpu.ops import sparse as sparse_ops

MODELS = ("fm", "ffm")
LOSSES = ("logistic", "squared")
OPTIMIZERS = ("sgd", "adagrad")


@dataclass(frozen=True)
class FMConfig:
    n_features: int                 # vocabulary size |V|
    n_fields: int = 1               # >1 + model="ffm" => field-aware
    k: int = 8                      # latent dimension
    max_nnz: int = 16               # static non-zero slots per instance
    model: str = "fm"
    loss: str = "logistic"
    learning_rate: float = 0.1
    l2: float = 0.0                 # on embeddings + linear weights
    init_scale: float = 0.01
    # "adagrad" is libffm's rule whole (:func:`train_step_adagrad`): the
    # chunk's SUMMED gradient, l2 on the touched parameters only, the
    # accumulator (started at ``adagrad_init``) first and then the step
    optimizer: str = "sgd"
    adagrad_init: float = 1.0

    def __post_init__(self):
        if self.model not in MODELS:
            raise Mp4jError(f"model must be one of {MODELS}")
        if self.loss not in LOSSES:
            raise Mp4jError(f"loss must be one of {LOSSES}")
        if self.model == "ffm" and self.n_fields < 2:
            raise Mp4jError("ffm needs n_fields >= 2")
        if self.optimizer not in OPTIMIZERS:
            raise Mp4jError(f"optimizer must be one of {OPTIMIZERS}")
        if self.optimizer == "adagrad" and self.model != "ffm":
            raise Mp4jError(
                "optimizer='adagrad' is libffm's rule, stated for the "
                "field-aware model: which entries a pair loop touches. "
                "model='fm' would need that reading for its "
                "sum-of-squares form, and a reference")


def _gather_slots(V, rows):
    """The embedding gather of the public table: per-slot rows of the
    (flat) [n_rows, k] table.

    rows come from :func:`_slot_rows` — [N, K] (fm) or [N, K, K]
    (ffm); result appends the latent dim k."""
    with jax.named_scope("ffm.table_gather"):
        return V[rows]


def _block_width(cfg: FMConfig) -> int:
    """Floats in a row of the step's table: the feature's vectors and
    its linear weight (:func:`_weight_column`), and under AdaGrad an
    accumulator for each of them in the row's second half
    (:func:`_weights_width`). An FFM block is padded
    with zeros to whole 128-lane words (39 fields x 4 and the weight:
    157 floats in 256; with their accumulators 314 in 384), so that the
    table rests row-major on the TPU, a
    feature's block one contiguous run: XLA keeps a [n_features, 156]
    parameter with the features on the lanes, and both the gather and
    the scatter then copy the whole table every step (AOT for v5e,
    ISSUE 27: 4.30 GB of temporaries). Pinning the layout of the
    unpadded shape (``jax.experimental.layout``) rests the same bytes
    the same way, but an executable read back from the persistent
    compilation cache reports its output in the default layout and the
    next call refuses it (my chip run, PR 27, jax 0.9.0). An FM row is
    its one vector and the weight."""
    if cfg.model == "fm":
        return cfg.k + 1
    live = cfg.n_fields * cfg.k + 1
    if cfg.optimizer == "adagrad":
        live *= 2
    return -(-live // 128) * 128


def _weights_width(cfg: FMConfig) -> int:
    """Columns of a block that hold parameters: all of an SGD block, the
    first half of an AdaGrad block. There the accumulator of the
    parameter in column c is column ``c + _weights_width``, so the
    forward and backward pass take the first half as it lies (the
    slots' gradients are [S, 192] at 39 x 4, no wider than SGD's), and
    the rule reads and writes the two halves side by side. Two tables
    [n_features, 256] would hold the same numbers in 8.59 GB for 6.44
    and double the rule's gather and scatter descriptors."""
    width = _block_width(cfg)
    return width // 2 if cfg.optimizer == "adagrad" else width


def _weight_column(cfg: FMConfig) -> int:
    """Column of a block that holds the feature's linear weight: the
    last of the parameters' columns (:func:`_weights_width`). No vector
    entry lives there: the k runs of ``stride`` columns
    (:func:`_block_stride`) either end before it or, where they fill
    the width, leave the last run's tail free (``width >= n_fields * k
    + 1`` makes ``stride > n_fields`` then), so ``_select_fields``
    never reads it as a vector's entry."""
    return _weights_width(cfg) - 1


def _block_stride(cfg: FMConfig) -> int:
    """Where a block keeps what: entry j of the vector against field
    ``fl`` is column ``j * stride + fl``, the k components one after the
    other, each a run of the fields. The public table keeps a vector's
    k entries on the sublanes and its rows on the lanes, so a
    component's run of a feature's fields is a run of lanes there too,
    and a conversion moves whole runs (TPU v5 lite, PR 27, the 2.62 GB
    table: 0.057 s a conversion; with the fields outermost, column
    ``fl * k + j``, every entry changes lane and it takes 0.266 s).
    :func:`_select_fields` keeps that order in what it hands on: its
    output columns run component by component too, each a run of the
    row's slots, so a component's [K, K] matrix of a row is a run of the
    matmul's neighbouring lanes and nothing downstream holds an array
    whose last dimension is the k components.
    An FM block has no runs: its vector, then the weight."""
    return _weights_width(cfg) // cfg.k


def _field_columns(cfg: FMConfig) -> np.ndarray:
    """[n_fields + 1, _weights_width] of 0/1: row ``fl`` marks the k
    columns ``j * stride + fl`` of the vector against field ``fl``, the
    last row the linear weight's column. Every column some row marks
    holds a parameter; the rest is padding, zero and never anything
    else."""
    marks = np.zeros((cfg.n_fields + 1, _weights_width(cfg)), np.float32)
    fl = np.arange(cfg.n_fields)
    for j in range(cfg.k):
        marks[fl, j * _block_stride(cfg) + fl] = 1.0
    marks[cfg.n_fields, _weight_column(cfg)] = 1.0
    return marks


def _gather_blocks(T, feats):
    """The one gather of the step's table ``T`` [n_features, block]:
    one descriptor a (sample, feature) brings the feature's vectors
    against every field and its linear weight; [N, K, block].

    What a descriptor costs goes with the LENGTH of the index list. XLA
    emits this gather 256 descriptors at a time (``integer_config`` 256,
    1 MB of scoped VMEM) where it pads the flattened list to the next
    1,024 itself (``pad_clamp_fusion``), and 128 at a time (256 KB)
    where the list is whole 1,024s already and needs no pad
    (``broadcast_clamp_fusion``): AOT for v5e, PR 40, every length
    tried. On the chip, this gather alone on a [4,194,304, 256] table:
    0.979 ms for 79,872 descriptors, 0.4535 for 80,184 (12.3 and 5.65 ns
    each; 13.6 and 6.39 at 384 columns; PERF.md section 6, PR 40). The
    scoring program's tile of 1,600 x 40 is on the wide side; a training
    chunk of 2,048 x 39 = 79,872 slots was on the narrow one. So the
    replicated steps take a chunk off the 1,024s before they call this
    (:func:`_with_dead_rows`); cutting a longer gather back here instead
    compiles to a ``slice`` of its own over the gathered blocks, one more
    pass over 82 MB."""
    with jax.named_scope("ffm.table_gather"):
        return T[feats]


# Rows a replicated step appends to a chunk whose slots are a whole number
# of 1,024s (:func:`_with_dead_rows`): a sublane's worth, so that the
# gathered [rows, K, block] still holds whole (8, 128) tiles. 2,048 x 39
# becomes 2,056 x 39 = 80,184 slots, 0.39% more.
_DEAD_ROWS = 8


def _dead_rows(slots: int) -> int:
    """Rows a replicated step adds to a member's chunk of ``slots`` slots
    (rows x ``max_nnz``, static at trace time)."""
    return _DEAD_ROWS if slots % 1024 == 0 else 0


def _with_dead_rows(batch):
    """``(batch, dead_slots)``: the step's batch with :func:`_dead_rows`
    dead rows after it, where its slots are a whole number of 1,024s, and
    the slots those rows hold. The table gather's index list is then one
    XLA pads itself and emits in its wide form (:func:`_gather_blocks`),
    and nothing is cut back afterwards: the select, the pair products and
    the backward run on that many rows more (cutting the gradient blocks
    back to the caller's rows compiles into the backward's last matmul
    and makes it run 1,600 times as long: 536 ms, my chip run, PR 40).
    A dead row is what ``fit_stream`` pads a short chunk with: ids,
    fields, values, mask, label and weight 0, so its kappa, its gradient
    blocks and its share of the loss's denominator are exactly 0.0. Its
    slots go into the merge under SENTINEL keys (the AdaGrad step keys
    every dead slot so; the SGD step these) and :func:`_merge_slots`
    drops them after the sort, so feature 0 gets no descriptor of them.
    Any other batch comes back as it is, and its step is the program it
    was."""
    dead = _dead_rows(batch[0].size)
    if dead:
        batch = tuple(
            jnp.concatenate([a, jnp.zeros((dead,) + a.shape[1:], a.dtype)])
            for a in batch)
    return batch, dead * batch[0].shape[1]


def _select_fields(blk, fields, cfg: FMConfig):
    """``(wv, E)`` out of the gathered blocks ``blk`` [N, K, block]:
    ``wv[n, a]`` the linear weight of feature a [N, K], and
    ``E[n, a, j, b] = v_{feat_a, field_b}[j]`` [N, K, k, K], component by
    component as the block rests (FM: the block's vector, [N, K, k]).

    A one-hot contraction over the block's columns. Its output columns
    run as the block's do (:func:`_block_stride`), k runs of K + 1:
    column ``j * (K + 1) + b`` takes column ``j * stride + field_b`` of
    feature a's block, and the last column of every run the weight's
    (:func:`_weight_column`; run 0's is handed on); the block's padding
    columns are never selected and get a gradient of 0.0. Exact in f32:
    each output is one block entry times 1.0 plus zeros (``HIGHEST``:
    the f32 value crosses the MXU as three bf16 pieces whose sum is the
    value; told that the one-hot needs one piece, ``(HIGHEST,
    DEFAULT)``, XLA takes the same 49 ms a 286,720-row chunk and gives
    the same bits: TPU v5 lite, PR 37). Not a second indexed gather,
    which would bring back a descriptor a slot pair, and not a slice
    where ``fields`` happens to count up: any assignment of fields to
    slots goes through the same contraction. Its transpose is what
    carries the gradient back into the block: two slots of a row in one
    field add, a field the row lacks gets exactly 0.0, and the weight's
    gradient lands in its column of the same array.

    The output is cut into its runs by one reshape and nothing is
    sliced off it as it lies: XLA turns it rows-minor ONCE for the pair
    product, and the weight is a slice of that copy's major dimensions.
    As one more column after the runs (until PR 37: columns ``b * k +
    j`` and the weight's, ``E`` [N, K, K, k]) the weight was a lane
    slice of the matmul's output ([N, K, 1], a lane of 128 in use) and a
    copy of its own: 3.5 + 3.9 ms of a chunk's 158.1, as much as the
    copy of the other 156 columns (9.5), where it now costs 1.7 on that
    copy (my chip runs, PR 37; PERF.md section 6). In the older form,
    contracting the columns as they lie ([N, K, block] x [N, block,
    K * k]) took 3.1 ms of a 2,048 x 39-slot step on TPU v5 lite (PR
    27); contracting a [N, K, n_fields, k] view over the fields 5.4, a
    compare-and-sum 5.8. The weight as a lane slice of ``blk`` beside
    the contraction, its gradient padded back into the block's width:
    0.48 ms a step more than as an output of the contraction (PR 31)."""
    wcol = _weight_column(cfg)
    if cfg.model == "fm":
        return blk[..., wcol], blk[..., :cfg.k]
    N, K, width = blk.shape
    runs = _block_stride(cfg) * jnp.arange(cfg.k, dtype=fields.dtype)
    cols = fields[:, None, :] + runs[:, None]                  # [N, k, K]
    cols = jnp.concatenate(
        [cols, jnp.full((N, cfg.k, 1), wcol, cols.dtype)], axis=2)
    sel = jax.nn.one_hot(cols.reshape(N, cfg.k * (K + 1)), width,
                         dtype=blk.dtype, axis=1)
    out = jnp.einsum("nac,ncm->nam", blk, sel,
                     precision=lax.Precision.HIGHEST)
    out = out.reshape(N, K, cfg.k, K + 1)
    return out[:, :, 0, K], out[..., :K]


def _select_build_args(cfg: FMConfig) -> dict:
    """What ``mp4j.step.build`` says of a program that holds
    :func:`_select_fields`: the order of the one-hot's output columns,
    so a trace says which form ran. FM has no select."""
    return dict(select_columns="component") if cfg.model == "ffm" else {}


def _by_component(E, cfg: FMConfig):
    """What a row form gathered (:func:`_slot_rows`: FFM [N, K, K, k], a
    row a slot pair) in the form :func:`_score_from_slots` reads,
    [N, K, k, K]; FM's [N, K, k] as it is."""
    return jnp.moveaxis(E, -1, 2) if cfg.model == "ffm" else E


def _score_from_slots(w0, wv, E, xv, cfg: FMConfig):
    """Model score given the already-gathered linear weights ``wv``
    [N, K] and the embedding entries ``E`` of every slot: FM [N, K, k];
    FFM [N, K, k, K], ``E[n, a, j, b]`` entry j of slot a's vector
    against slot b's field (what :func:`_select_fields` hands on; the
    row forms gather [N, K, K, k] and pass it through
    :func:`_by_component`).

    Split out from :func:`_score` so the sparse train step can
    differentiate with respect to what it gathered DIRECTLY (per-slot
    gradient rows) instead of the full table — the backward of a table
    gather is a dense scatter-add over |V| rows on the serial scatter
    unit."""
    linear = jnp.sum(wv * xv, axis=1)
    if cfg.model == "fm":
        # 0.5 * ((sum_a v_a x_a)^2 - sum_a (v_a x_a)^2), summed over k
        Ex = E * xv[..., None]                         # [N, K, k]
        s = jnp.sum(Ex, axis=1)                        # [N, k]
        inter = 0.5 * jnp.sum(s * s - jnp.sum(Ex * Ex, axis=1), axis=1)
    else:
        # FFM: z += sum_j E[a, j, b] E[b, j, a] x_a x_b over a < b: each
        # component's [K, K] matrix times its own transpose
        pair = jnp.einsum("najb,nbja->nab", E, E)
        pair = pair * (xv[:, :, None] * xv[:, None, :])
        K = xv.shape[1]
        upper = jnp.triu(jnp.ones((K, K), pair.dtype), 1)
        inter = jnp.sum(pair * upper, axis=(1, 2))
    return w0 + linear + inter


def _score_gathered(p, fields, xv, cfg: FMConfig):
    """The margin a sparse training step differentiates, from ``p`` =
    ``(w0, blk)`` with the gathered blocks: :func:`_select_fields` under
    the scope ``ffm.select``, :func:`_score_from_slots` under
    ``ffm.pairs`` (the loss joins it there: :func:`_weighted_mean_grads`).
    Inside ``value_and_grad`` a device trace shows the forward pass as
    ``jvp(ffm.select)`` and the backward as ``transpose(jvp(ffm.select))``.
    Not ``ffm.score.*``: those are the scoring program's (:func:`predict`),
    and its metrics read them."""
    with jax.named_scope("ffm.select"):
        wv, E = _select_fields(p[1], fields, cfg)
    with jax.named_scope("ffm.pairs"):
        return _score_from_slots(p[0], wv, E, xv, cfg)


def _score(params, feats, fields, vals, mask, cfg: FMConfig):
    """Model score for a batch of padded sparse instances, from the
    public [n_rows, k] table.

    feats/fields: [N, K] int32; vals/mask: [N, K] f32.
    """
    w0, w, V = params
    xv = vals * mask                                   # zero padded slots
    E = _by_component(_gather_slots(V, _slot_rows(feats, fields, cfg)), cfg)
    return _score_from_slots(w0, w[feats], E, xv, cfg)


def _score_blocks(state, feats, fields, vals, mask, cfg: FMConfig):
    """:func:`_score` from the step's ``(w0, T)``, the table by feature
    [n_features, block] (AdaGrad's state holds more, which no score
    reads)."""
    w0, T = state[:2]
    blk = _gather_blocks(T, feats)[..., :_weights_width(cfg)]
    wv, E = _select_fields(blk, fields, cfg)
    return _score_from_slots(w0, wv, E, vals * mask, cfg)


def _slot_rows(feats, fields, cfg: FMConfig):
    """Row of the public [n_rows, k] table touched by each slot.

    FM touches row ``feat`` per slot ([N, K]); FFM touches row
    ``feat * n_fields + field_b`` per slot PAIR ([N, K, K]) — matching
    the [N, K(, K), k] layout of ``_score``'s gathers. The dense step
    and the sharded table's ``predict`` index this way; the sparse steps,
    sharded table or not, and the replicated table's ``predict`` index
    by feature (:func:`_gather_blocks`).
    """
    if cfg.model == "fm":
        return feats
    return feats[:, :, None] * cfg.n_fields + fields[:, None, :]


def _pcast_params(params, axis_name):
    """Cast params device-varying so grads stay per-shard and the
    cross-shard reduction is the explicit collective chosen by the
    caller (dense psum or sparse allreduce) — see models/linear.py."""
    if axis_name is None:
        return params
    return jax.tree_util.tree_map(
        lambda p: lax.pcast(p, axis_name, to="varying"), params)


def _weighted_mean_grads(p, score_fn, y, sw, cfg: FMConfig, axis_name):
    """Global-mean loss + grads of the sample-weighted shard loss —
    the one prologue shared by the dense and sparse steps. ``p`` is
    the differentiated pytree (full params; (w0, blk) with the gathered
    blocks on the sparse paths); ``score_fn(p)`` the margin. The loss
    runs under the scope ``ffm.pairs``, where :func:`_score_gathered`
    puts the margin's last lines (the dense step's loss too, the only
    name its backward pass carries)."""
    def shard_sum(q):
        z = score_fn(q)
        # the loss is the pair products' last lines in a device trace
        with jax.named_scope("ffm.pairs"):
            return jnp.sum(per_example_loss(z, y, cfg.loss) * sw)

    sum_loss, grads = jax.value_and_grad(shard_sum)(p)
    cnt = jnp.sum(sw)
    if axis_name is not None:
        sum_loss = lax.psum(sum_loss, axis_name)
        cnt = lax.psum(cnt, axis_name)
    denom = jnp.maximum(cnt, 1.0)
    return sum_loss / denom, grads, denom


def _mean_loss_grad(params, batch, cfg: FMConfig, axis_name):
    feats, fields, vals, mask, y, sw = batch
    params = _pcast_params(params, axis_name)
    return _weighted_mean_grads(
        params, lambda p: _score(p, feats, fields, vals, mask, cfg),
        y, sw, cfg, axis_name)


def train_step_dense(params, batch, cfg: FMConfig, axis_name=None):
    """One step; the embedding-gradient allreduce is a dense psum."""
    loss, (g0, gw, gV), denom = _mean_loss_grad(params, batch, cfg, axis_name)
    if axis_name is not None:
        g0 = lax.psum(g0, axis_name)
        gw = lax.psum(gw, axis_name)
        gV = lax.psum(gV, axis_name)       # THE dense gradient allreduce
    w0, w, V = params
    lr = cfg.learning_rate
    w0 = w0 - lr * (g0 / denom)
    w = w - lr * (gw / denom + cfg.l2 * w)
    V = V - lr * (gV / denom + cfg.l2 * V)
    return (w0, w, V), loss


def _check_block_table(params, n_arrays: int, cfg: FMConfig,
                       sharded: bool = False):
    """A block step's ``params``: ``n_arrays`` of them, the second the
    table by feature (``sharded``: a member's share of its rows)."""
    shape = getattr(params[1], "shape", ()) if len(params) > 1 else ()
    rows_ok = len(shape) == 2 and (sharded or shape[0] == cfg.n_features)
    if (len(params) != n_arrays or not rows_ok
            or shape[1] != _block_width(cfg)):
        # the public [n_rows, k] table would index and compile too, as
        # n_rows features of one k-wide block: another model
        raise Mp4jError(
            "the sparse step takes (w0, T, ...), the table by feature, "
            f"[{cfg.n_features}, {_block_width(cfg)}] "
            "(FMTrainer._enter converts the public params), got "
            f"{[getattr(p, 'shape', None) for p in params]}")


def train_step_sparse(params, batch, cfg: FMConfig, capacity: int,
                      axis_name="mp4j"):
    """One step; embedding gradients ride the SPARSE path.

    ``params`` is ``(w0, T)`` with the table in the step's form,
    ``T`` [n_features, block]: a feature's vectors against every field
    side by side and its linear weight in the last column
    (:func:`_block_width`, :func:`_weight_column`). Instead of
    psum'ing the dense gradient table, each shard ships its
    ``(feature, grad_block)`` slots over ONE all_gather each, the slots
    are summed by feature (:func:`_merge_slots`: one sort, one segmented
    sum) and the merged list's LIVE PREFIX is scatter-added into T a
    tile of :func:`_update_tile` at a time
    (``ops/sparse.fold_live_tiles``, the donated table the loop's carry,
    updated where it rests): the shape of :func:`train_step_adagrad`
    and of the sharded step's owner side, with a scatter-add for a
    body. A (sample, feature) is ONE gather descriptor and a distinct
    feature ONE scatter descriptor whatever ``n_fields``, the weight
    included: the serial unit charges by the descriptor, live or
    dropped (PERF.md section 5), so a chunk pays for the features it
    holds, rounded up to a tile, and not for its slots; the step reads,
    reduces and writes nothing else of size ``n_features``. SGD's
    update is linear in the gradient, so every slot's gradient still
    reaches its feature, summed in f32 before the add instead of by it.
    ``capacity`` is the merged list's static length, in features: all
    the slots of every shard by default, so that nothing is dropped; a
    smaller one must hold the distinct features of a step.

    The table enters autodiff only through the GATHERED blocks
    (``_select_fields`` + ``_score_from_slots``), so the backward
    yields the per-slot gradient blocks [S, block] directly, the
    weight's gradient in its column — differentiating through the
    gather would scatter-add a dense |V|-row gradient table on the
    serial scatter unit and immediately re-gather its touched rows
    (1.8x the step time at 8M rows on the previous installation; not
    measured on this chip).
    """
    (feats, fields, vals, mask, y, sw), dead = _with_dead_rows(batch)
    _check_block_table(params, 2, cfg)
    w0, T = _pcast_params(params, axis_name)
    blk = _gather_blocks(T, feats)              # [N, K, block]
    xv = vals * mask
    loss, (g0, gblk), denom = _weighted_mean_grads(
        (w0, blk),
        lambda p: _score_gathered(p, fields, xv, cfg),
        y, sw, cfg, axis_name)
    if axis_name is not None:
        g0 = lax.psum(g0, axis_name)

    S = feats.size
    keys = feats.reshape(-1).astype(jnp.int32)
    if dead:
        keys = keys.at[S - dead:].set(sparse_ops.SENTINEL)
    with jax.named_scope("ffm.grad_merge"):
        # [N, K, block] to the merge's [S, block] is a copy where K is not
        # whole 8s (39 rests padded to 40): the merge's, not nobody's
        payload = gblk.reshape(S, -1)
    ui, uv = _merge_slots(keys, payload, capacity, axis_name, dead)
    lr = cfg.learning_rate
    w0 = w0 - lr * (g0 / denom)
    if cfg.l2:
        # decay all rows, like the dense step: vectors and weights alike
        # (the padding columns stay 0.0)
        T = T * (1.0 - lr * cfg.l2)
    scale = -(lr / denom)

    def add_tile(T, ti, tv):
        # NOT told that a tile's ids ascend and are distinct: told, XLA
        # passes over the whole table a call (14.3 ms at 4.29 GB, PR 35)
        with jax.named_scope("ffm.table_update"):
            return T.at[jnp.where(ti == sparse_ops.SENTINEL, T.shape[0],
                                  ti)].add(scale * tv, mode="drop")

    T = sparse_ops.fold_live_tiles(ui, uv, _update_tile(capacity),
                                   add_tile, T)
    return (w0, T), loss


def _touch_counts(fields, mask, sw, cfg: FMConfig):
    """``(live, cnt)``: ``live`` [N, K] is 1.0 for a slot that holds a
    feature (``mask > 0``) in a row that counts (``sw > 0``), and
    ``cnt[n, a]`` [N, K, n_fields + 1] says what of feature a's block
    the row's pair loop reaches, as libffm's does: ``cnt[n, a, fl]`` is
    the number of OTHER live slots of the row in field ``fl`` (the
    vector ``v[feat_a, fl]`` meets each of them; with one feature a
    field, never the vector against the feature's own field), and the
    last entry is ``live`` itself, for the linear weight."""
    live = ((mask > 0) & (sw[:, None] > 0)).astype(jnp.float32)
    held = jax.nn.one_hot(fields, cfg.n_fields,
                          dtype=jnp.float32) * live[..., None]
    others = live[..., None] * (jnp.sum(held, axis=1, keepdims=True) - held)
    return live, jnp.concatenate([others, live[..., None]], axis=-1)


def _touched_columns(cnt, cfg: FMConfig):
    """[C, n_fields + 1] counts of a feature (:func:`_touch_counts`,
    summed over its slots) -> [C, _weights_width] bool, the block's
    columns that some row touched: column ``j * stride + fl`` for every
    j where field ``fl`` was met, and the weight's column. A product
    with :func:`_field_columns`, which copies a count into its k
    columns; any count above zero stays above zero at any matmul
    precision."""
    return (cnt @ _field_columns(cfg)) > 0


def _merge_slots(keys, payload, capacity: int, axis_name, dead: int = 0):
    """Every shard's ``(feature, payload)`` slots -> at most ``capacity``
    DISTINCT features, ascending, each with the sum of its slots'
    payloads; SENTINEL keys are dropped and pad the tail. This is
    ``ops/sparse.sparse_allreduce``'s shape: the ``all_gather`` first,
    because two shards that both saw a feature must sum before a rule
    that is not linear in the gradient, then one sort and one segmented
    reduction. Both replicated steps call it (the SGD step for the
    descriptors it saves, the AdaGrad step because its rule needs the
    sum) and hand the list to ``ops/sparse.fold_live_tiles``.

    ``dead`` of a shard's slots are its step's dead rows'
    (:func:`_with_dead_rows`): SENTINEL-keyed, so the last of the sorted
    order, and cut from it before the payload is gathered by it. The
    segmented sum then runs on the callers' slots, lists that are whole
    1,024s as they were (on the Criteo cells' 80,184 it took 0.27 ms
    longer than on their 79,872, more than the SGD step's gather had
    saved: my chip runs, PR 40)."""
    own = keys.shape[0]
    with jax.named_scope("ffm.grad_merge"):
        if axis_name is not None:
            keys = lax.all_gather(keys, axis_name, axis=0, tiled=True)
            payload = lax.all_gather(payload, axis_name, axis=0, tiled=True)
        keep = keys.shape[0] // own * (own - dead) if dead else None
        si, sv = sparse_ops.sort_by_key(keys, payload, keep=keep)
        return sparse_ops.segment_reduce_sorted(si, sv, capacity,
                                                Operators.SUM)


def _adagrad(p, G, g, lr):
    """``(p, G)`` after libffm's update for the gradient ``g``: the
    accumulator first, then the step by the NEW accumulator."""
    G = G + g * g
    return p - lr * g / jnp.sqrt(G), G


# Distinct features a replicated step's update loop takes a trip: the
# AdaGrad step gathers, updates and sets them back, the SGD step
# scatter-adds them. Under AdaGrad a descriptor costs 102 ns through
# gather, rule and scatter, live or dropped, and a chunk overshoots its
# live count by half a tile on average; a trip costs about 3 us of its
# own. At the Criteo cell's shape (79,872 slots, 33,940 live) that is
# flat from 512 to 2,048 and this is the fastest of the twelve tiles
# swept on the chip, 128 to 79,872 (PERF.md section 5, PR 33; swept again
# at PR 35, the merge 0.41 ms shorter and the descriptor's price where it
# was: the same order, 9.960 ms a step here, 9.982 at 1,536, 9.983 at
# 512). The SGD step is as flat (PR 39: 7.855 ms a step at 512, 7.818
# here, 7.810 at 1,024, 7.848 at 1,536), so both share the one tile.
_UPDATE_TILE = 768


def _update_tile(capacity: int) -> int:
    """The tile of a replicated step's update loop for a merged list of
    ``capacity`` entries."""
    return min(_UPDATE_TILE, capacity)


def train_step_adagrad(params, batch, cfg: FMConfig, capacity: int,
                       axis_name="mp4j"):
    """One step of libffm's rule (Juan et al., RecSys 2016, Algorithm 1)
    on the replicated table, a chunk at a time.

    ``params`` is ``(w0, T, a0)``: the bias, the table by feature with
    every parameter's accumulator ``_weights_width`` columns to its
    right, and the bias's accumulator. With ``kappa_n = sw_n *
    dloss/dz_n`` (a SUM over the chunk's rows, all shards', not a mean:
    beside G = 1 a mean's square vanishes in f32), a parameter p that a
    live row's pair loop reaches (:func:`_touch_counts`) gets

        g = sum_n kappa_n dz_n/dp + l2 * p;  G += g * g;  p -= lr * g / sqrt(G)

    and every other parameter and accumulator keeps its bits; ``l2``
    costs nothing of size ``n_features``. The bias has no ``l2``. The
    reported loss is the weighted mean, as the SGD step's.

    The rule squares g, so a feature's slots are summed BEFORE it
    (:func:`_merge_slots`: the map plane's sort and segmented reduce,
    which the SGD step runs too, for the descriptors it saves): a
    feature a chunk holds 200 times gets one update, not 200.
    ``capacity`` bounds the distinct features and must be all the slots
    there are (or ``n_features``), so that the merge drops nothing.

    The merged list is ascending, the distinct features first and
    SENTINEL after them, and the serial unit charges a dropped sentinel
    what it charges a live descriptor (97.9 ns a row of a tile of 768
    set alone, sentinel tail or none). The scatter is not told that a
    tile's ids ascend: told, XLA passes over the whole table a call
    (19.4 ms at 6.44 GB, whatever the tile). So the distinct features' blocks
    are gathered, updated and SET back (no index repeats) a tile of
    :func:`_update_tile` at a time, over the list's LIVE PREFIX only
    (``ops/sparse.fold_live_tiles``: the trip count is the live count's,
    read from the list): a chunk pays for the features it holds, rounded
    up to a tile, and not for its ``capacity`` slots. The loop carries
    the donated table, which stays where it rests; tiles are disjoint,
    so every reached parameter is still updated exactly once a chunk; the
    sentinels of the last tile reached are dropped by the scatter, one
    descriptor each way a distinct feature."""
    (feats, fields, vals, mask, y, sw), dead = _with_dead_rows(batch)
    _check_block_table(params, 3, cfg)
    w0, T, a0 = _pcast_params(params, axis_name)
    hw = _weights_width(cfg)
    blk = _gather_blocks(T, feats)[..., :hw]        # [N, K, hw]
    xv = vals * mask
    loss, (g0, gblk), _ = _weighted_mean_grads(
        (w0, blk),
        lambda p: _score_gathered(p, fields, xv, cfg),
        y, sw, cfg, axis_name)
    if axis_name is not None:
        g0 = lax.psum(g0, axis_name)

    S = feats.size
    live, cnt = _touch_counts(fields, mask, sw, cfg)
    keys = jnp.where(live > 0, feats, sparse_ops.SENTINEL).reshape(-1)
    with jax.named_scope("ffm.grad_merge"):
        payload = jnp.concatenate(
            [gblk.reshape(S, hw), cnt.reshape(S, -1)], axis=1)
    ui, uv = _merge_slots(keys, payload, capacity, axis_name, dead)

    lr = cfg.learning_rate

    def update_tile(T, ti, tv):
        dead = ti == sparse_ops.SENTINEL
        with jax.named_scope("ffm.table_gather"):
            cur = T[jnp.where(dead, 0, ti)]             # [tile, block]
        with jax.named_scope("ffm.adagrad_rule"):
            p, G = cur[:, :hw], cur[:, hw:]
            touched = _touched_columns(tv[:, hw:], cfg)
            g = jnp.where(touched, tv[:, :hw] + cfg.l2 * p, 0.0)
            stepped, G = _adagrad(p, G, g, lr)  # untouched: G + 0.0, its bits
            new = jnp.concatenate(
                [jnp.where(touched, stepped, p), G], axis=1)
        with jax.named_scope("ffm.table_update"):
            return T.at[jnp.where(dead, T.shape[0], ti)].set(
                new, mode="drop")

    # the loop is ``sparse.fold_live_tiles`` in a device trace; gather,
    # rule and update are read apart inside it (PERF.md section 3)
    T = sparse_ops.fold_live_tiles(ui, uv, _update_tile(capacity),
                                   update_tile, T)
    with jax.named_scope("ffm.adagrad_rule"):
        w0, a0 = _adagrad(w0, a0, g0, lr)
    return (w0, T, a0), loss


def _fetch_rows_sharded(Vs, flat_rows, me, axis_name):
    """Owner-routed row fetch from a block-sharded table: every
    member's row-ids ride one (tiny, int32) all_gather, owners answer
    with their rows over one ``all_to_all``, and the per-owner
    contributions sum to the complete rows (each id is owned by exactly
    one member). Returns ([S, k] rows for THIS member's ids, gi [n, S]
    all requests, owner [n, S]) — the latter two are reused by the
    train step's backward routing."""
    B, _k = Vs.shape
    gi = lax.all_gather(flat_rows, axis_name, axis=0,
                        tiled=False)            # [n, S] all requests
    owner = gi // B
    local = jnp.where(owner == me, gi - me * B, 0)
    with jax.named_scope("ffm.table_gather"):
        contrib = Vs[local]                     # [n, S, k] row gather
    contrib = jnp.where((owner == me)[..., None], contrib, 0.0)
    recv = collectives.all_to_all(contrib, axis_name)   # [n, S, k]
    return jnp.sum(recv, axis=0), gi, owner


# A member asks one owner for at most this many distinct features' blocks
# a round of the sharded step's exchange, so the three buffers a member
# holds ([n, cap, block]: the answers it gathers, what comes back, the
# gradients it sends) are 67 MB each on four members at 39 fields x 4. A
# step whose fullest (requester, owner) pair holds more runs another round
# (:func:`train_step_sparse_sharded`). 16,384 is 1.7 times what a member
# asks of an owner at the Criteo cell's shape (about 9,400 of a chunk's
# 79,872 slots at Zipf 1.1 over four owners; PERF.md section 4).
_EXCHANGE_CAP = 16384

# Blocks an owner gathers, or scatter-adds, a trip of its walk over the
# live prefix of one member's list; ``_EXCHANGE_CAP`` is whole tiles. The
# step alone on one chip (one member, 2^23 features, one list of 37,600
# live ids; my chip run, PR 38), ms a step by tile: 512: 8.884; 1,024:
# 8.956; 2,048: 9.020; 4,096: 9.002 (a trip's own cost against half a tile
# of dropped sentinels a list, as ``_UPDATE_TILE`` weighs them).
_SHARD_TILE = 512


def _exchange_cap(per_shard_slots: int) -> int:
    """The sharded step's ``cap`` for a member's ``per_shard_slots``
    slots: a member cannot hold more distinct features than slots, so a
    short batch gets buffers of its own length (whole tiles) and one round
    whatever its skew."""
    tile = min(_SHARD_TILE, per_shard_slots)
    return min(_EXCHANGE_CAP, -(-per_shard_slots // tile) * tile)


def _shard_tile(cap: int) -> int:
    """The tile of the owner's walks over lists of ``cap`` ids: whole
    tiles, or the list at once."""
    return _SHARD_TILE if cap % _SHARD_TILE == 0 else cap


def train_step_sparse_sharded(params, batch, cfg: FMConfig, n: int,
                              cap: int, axis_name="mp4j"):
    """One SGD step with the table SHARDED by feature over the ``n``
    members of the mesh, in the block form of :func:`train_step_sparse`.

    ``params`` is ``(w0, Ts, rounds)``: the bias, this member's shard
    ``Ts`` [B, block] of the table by feature (member m holds the blocks
    of features ``[m * B, (m + 1) * B)``, linear weights in their column:
    a boundary never cuts a block, and the step holds nothing of size
    ``n_features``), and the count of exchange rounds so far (below).
    Rows are data-parallel as everywhere else. A step is

    - *route* (scope ``ffm.shard.route``): the member's live slots' ids,
      sorted and made distinct (``ops/sparse``: one sort, one pass).
      Ownership is monotone in the id, so the ascending list is already
      bucketed by owner: an owner's requests are a slice of it.
    - *fetch*: the ids go to their owners (``all_to_all``, [n, cap]
      int32), each owner gathers the blocks asked of it from its shard
      (scope ``ffm.table_gather``; one descriptor a distinct feature a
      requester, walked over each list's live prefix a tile at a time:
      what the chunk touches of what the member owns, not n x S), a
      second ``all_to_all`` brings them back, and the member spreads the
      distinct blocks over its slots (``ffm.shard.spread``).
    - score and gradient: the replicated step's own functions on the
      [N, K, block] blocks.
    - *merge and return*: the slots' gradient blocks are summed by
      distinct feature in the same sorted order (``ffm.grad_merge``),
      sent to their owners by ``all_to_all``, and the owner scatter-adds
      each member's list into its shard (``ffm.table_update``, live
      prefix again). The lists are not merged with each other first: SGD
      is linear in the gradient, so a feature that two members touched
      is two descriptors (about a fifth more at the Criteo cell's shape)
      where a merge would cost a sort of n x cap blocks.

    Nothing is dropped, whatever the skew: a member sends an owner ``cap``
    ids a round, and a step in which some (requester, owner) pair holds
    more runs ``ceil(most / cap)`` rounds of the same exchange, the count
    agreed by a ``pmax`` so every member joins every collective. A chunk
    whose every slot belongs to one owner gives what the replicated step
    gives. ``rounds`` is carried so that a caller can say how many ran
    (``FMTrainer.exchange_rounds_``).

    Dead slots (padding, or a row of weight 0) ask for nothing and get a
    block of zeros; a feature nobody's live slot holds is not touched.
    ``l2`` decays every row, as the replicated step does.

    Measured (four TPU v5 lite, PR 38, 2^25 features, a chunk of 8,192
    rows, 37,700 distinct features a member; PERF.md section 5): a step
    10.52 ms, of which the owner's scatter-add 3.41, the gradients'
    merge 2.13, select and backward 1.62, the four exchanges 1.53 (all
    of it exposed: XLA emits them as synchronous operations), route 0.76,
    spread 0.66, the owner's gather 0.25; one member alone on a chip's
    share of the table 8.08."""
    feats, fields, vals, mask, y, sw = batch
    _check_block_table(params, 3, cfg, sharded=True)
    w0, Ts, rounds_run = params
    w0 = lax.pcast(w0, axis_name, to="varying")
    B, width = Ts.shape
    me = collectives.flat_index(axis_name)
    S = feats.size
    i32 = jnp.int32
    tile = _shard_tile(cap)
    lane = jnp.arange(cap, dtype=i32)

    with jax.named_scope("ffm.shard.route"):
        live = (mask > 0) & (sw[:, None] > 0)
        keys = jnp.where(live, feats, sparse_ops.SENTINEL).reshape(-1)
        sk, perm = sparse_ops.sort_by_key(keys.astype(i32),
                                          jnp.arange(S, dtype=i32))
        uk, seg = sparse_ops.distinct_sorted(sk, S)
        # seg in the slots' own order: where a slot's block is in the list
        _, inv = sparse_ops.sort_by_key(perm, seg)
        owner = jnp.where(uk == sparse_ops.SENTINEL, n, uk // B)
        count = jnp.sum(owner[None, :] == jnp.arange(n, dtype=i32)[:, None],
                        axis=1, dtype=i32)              # [n] asked of each
        start = jnp.cumsum(count) - count
        rounds = lax.pmax((jnp.max(count) + (cap - 1)) // cap, axis_name)
        # a slice that starts in the list's last cap entries must not be
        # pulled back over the ones before it
        ukp = jnp.concatenate(
            [uk, jnp.full((cap,), sparse_ops.SENTINEL, i32)])

    def asked_of(r):
        """[n, cap]: the ids this member asks of each owner in round r,
        ascending, SENTINEL after them."""
        with jax.named_scope("ffm.shard.route"):
            return jnp.stack([
                jnp.where(lane < count[m] - r * cap,
                          lax.dynamic_slice_in_dim(
                              ukp, start[m] + r * cap, cap),
                          sparse_ops.SENTINEL)
                for m in range(n)])

    def local(ids, dead_as):
        """Rows of this shard for ids it owns; ``dead_as`` for SENTINEL."""
        return jnp.where(ids == sparse_ops.SENTINEL, dead_as, ids - me * B)

    def fetch(r, ublk):
        asks = collectives.all_to_all(asked_of(r), axis_name)   # [n, cap]

        def gather_tile(out, ti, at):
            got = _gather_blocks(Ts, local(ti, 0))
            return lax.dynamic_update_slice_in_dim(out, got, at[0], axis=0)

        answers = jnp.zeros((n * cap, width), Ts.dtype)
        for j in range(n):
            answers = sparse_ops.fold_live_tiles(
                asks[j], lane + j * cap, tile, gather_tile, answers)
        back = collectives.all_to_all(
            answers.reshape(n, cap, width), axis_name)
        with jax.named_scope("ffm.shard.spread"):
            for m in range(n):      # owner m's answers, into their slice
                at = start[m] + r * cap
                mine = (lane < count[m] - r * cap)[:, None]
                ublk = lax.dynamic_update_slice_in_dim(
                    ublk, jnp.where(mine, back[m],
                                    lax.dynamic_slice_in_dim(ublk, at, cap)),
                    at, axis=0)
        return ublk

    # the distinct features' blocks, as ``uk`` lists them (the sentinel
    # segment's, and the tail a last slice runs into: zeros)
    ublk = lax.fori_loop(0, rounds, fetch,
                         jnp.zeros((S + cap, width), Ts.dtype))
    with jax.named_scope("ffm.shard.spread"):
        blk = ublk[inv].reshape(feats.shape + (width,))     # [N, K, block]

    xv = vals * mask
    loss, (g0, gblk), denom = _weighted_mean_grads(
        (w0, blk),
        lambda p: _score_gathered(p, fields, xv, cfg),
        y, sw, cfg, axis_name)
    g0 = lax.psum(g0, axis_name)

    with jax.named_scope("ffm.grad_merge"):
        _, ug = sparse_ops.segment_reduce_sorted(
            sk, gblk.reshape(S, width)[perm], S + cap, Operators.SUM)

    lr = cfg.learning_rate
    if cfg.l2:
        Ts = Ts * (1.0 - lr * cfg.l2)
    scale = -(lr / denom)

    def give(r, Ts):
        ids = collectives.all_to_all(asked_of(r), axis_name)
        with jax.named_scope("ffm.shard.route"):
            sends = jnp.stack([
                lax.dynamic_slice_in_dim(ug, start[m] + r * cap, cap)
                for m in range(n)])
        grads = collectives.all_to_all(sends, axis_name)    # [n, cap, block]

        def add_tile(Ts, ti, tv):
            # a list's ids are distinct; what follows them is SENTINEL,
            # whatever the sender's slice ran into: dropped
            with jax.named_scope("ffm.table_update"):
                return Ts.at[local(ti, B)].add(scale * tv, mode="drop")

        for j in range(n):
            Ts = sparse_ops.fold_live_tiles(ids[j], grads[j], tile,
                                            add_tile, Ts)
        return Ts

    Ts = lax.fori_loop(0, rounds, give, Ts)
    w0 = w0 - lr * (g0 / denom)
    return (w0, Ts, rounds_run + rounds), loss


# Rows of a 32-bit array's tile on the TPU: an [N, K, block] array rests
# with K padded to whole 8s, so [N * K, block] is [N, K, block] as it
# lies only where K is whole 8s.
_SUBLANES = 8


def predict(state, feats, fields, vals, cfg: FMConfig):
    """What the model says of a batch of padded sparse instances, from
    the entered ``(w0, T)`` (the table by feature, a parameters-only
    block a row: :func:`_scoring_cfg`): the probability under the
    logistic loss, else the score. A padded slot carries the value 0,
    so ``vals`` is its own mask. One descriptor a (row, slot) brings the
    feature's block (:func:`_gather_blocks`); the row form's
    (:func:`_score`) gather is a descriptor a slot PAIR.

    The slots are first padded to whole ``_SUBLANES`` with empty ones
    (feature 0, field 0, value 0: they score exactly nothing). The
    gather leaves its blocks as [N * K, block]; with K whole 8s that IS
    [N, K, block] and the select reads the blocks where the gather put
    them. At K = 39 XLA rewrote every tile's 61 MB into the padded form
    between the two (TPU v5 lite, PR 37: ``reshape``, 18.0 ms of a
    286,720-row chunk's 158.1, 0.38 s of a 6,042,135-row job; one more
    descriptor in forty costs 1.6 of the gather's 63.7)."""
    w0, T = state
    pad = -feats.shape[1] % _SUBLANES
    if pad:
        feats, fields, vals = (jnp.pad(a, ((0, 0), (0, pad)))
                               for a in (feats, fields, vals))
    blk = _gather_blocks(T, feats)
    with jax.named_scope("ffm.score.select"):
        wv, E = _select_fields(blk, fields, cfg)
    with jax.named_scope("ffm.score.pairs"):
        z = _score_from_slots(w0, wv, E, vals, cfg)
        return jax.nn.sigmoid(z) if cfg.loss == "logistic" else z


# Rows :func:`score_rows` takes through :func:`predict` a trip of its
# loop. Swept on the chip as ``_UPDATE_TILE`` was: the scoring program
# alone on one staged chunk of 286,720 rows at the Criteo cell's shape
# (39 slots padded to 40, 39 fields x 4, the 4.29 GB table), PR 36's 26
# tiles and nine odd multiples of 64 between them, ms a chunk, the best
# of four (my chip runs, PR 37; PERF.md section 5): 64: 216.6, 128: 239.2,
# 192: 192.7, 256: 239.5, 320: 187.5, 384: 229.6, 448: 212.0, 512: 241.2,
# 576: 194.0, 640: 233.5, 768: 229.0, 896: 236.4, 1,024: 235.9, 1,088:
# 187.5, 1,152: 228.3, 1,216: 196.2, 1,280: 236.8, 1,344: 192.7, 1,408:
# 233.6, 1,472: 141.3, 1,536: 211.8, 1,600: 139.1, 1,664: 215.1, 1,728:
# 141.3, 1,792: 183.9, 1,856: 141.8, 1,920: 186.7, 1,984: 144.0, 2,048:
# 186.1, 2,112: 143.0, 2,304: 217.2, 2,560: 219.0, 3,072: 235.1, 3,584:
# 236.6, 4,096: 235.3. Two of XLA's choices decide a tile's time, and a
# new jax or libtpu can move both. Where XLA does not pad a tile's index
# list (every even multiple of 64 at 40 slots; PR 36's "whole multiples
# of 1,024" at 39) the gather fusion takes descriptors 128 at a time and
# not 256 and runs at 9.4 to 12.2 ns a descriptor, not 5.6. And up to
# 1,408 rows the gathered blocks rest in VMEM, where the select's matmul
# takes them a row an iteration and not six (100 ms a chunk, not 49);
# from 1,472 on they rest in HBM.
_SCORE_TILE = 1600


def _score_tile(rows: int) -> int:
    """The tile of :func:`score_rows`'s loop for a call of ``rows``."""
    return min(_SCORE_TILE, rows)


def score_rows(feats, fields, vals, state, out, start, cfg: FMConfig,
               skip: int = 0):
    """Score the rows of a piece of instances, ``feats``, ``fields``
    [R, max_nnz] int32 and ``vals`` [R, max_nnz] f32, from row ``skip``
    on, and write what :func:`predict` says of them into ``out`` [N] f32
    from row ``start`` on (the other rows are passed on; ``start`` only
    says where the results go). ``state`` is the entered ``(w0, T)``.

    The rows go through in tiles of :func:`_score_tile`, so that what
    the program holds beside its arguments is a tile's and not the
    call's (a piece of 286,720 rows would gather 11.5 GB of blocks).
    The last tile is as long as the others: it starts early and
    scores rows again that the one before it scored, to the same bits (a
    row's score takes nothing from its neighbours)."""
    rows, K = feats.shape[0] - skip, feats.shape[1]
    tile = _score_tile(rows)

    def score_tile(c, out):
        at = jnp.minimum(c * tile, rows - tile)
        part = [lax.dynamic_slice(a, (skip + at, jnp.zeros((), at.dtype)),
                                  (tile, K)) for a in (feats, fields, vals)]
        return lax.dynamic_update_slice(out, predict(state, *part, cfg),
                                        (start + at,))

    # the loop itself is under no scope: gather, select and pairs are
    # read apart in a device trace (PERF.md section 3)
    return lax.fori_loop(0, -(-rows // tile), score_tile, out)


def _scoring_cfg(cfg: FMConfig) -> FMConfig:
    """The configuration whose block is what scoring reads: parameters
    only, whatever the rule that trained them ([n_features, 256] at 39
    fields x 4; AdaGrad's step carries 384, its accumulators beside the
    parameters, and no score reads those)."""
    return replace(cfg, optimizer="sgd")


class EnteredModel(NamedTuple):
    """A model as ``FMTrainer.predict`` scores it, on the trainer's
    mesh: the bias and the table by feature (:func:`_scoring_cfg`'s
    block a row, the linear weight in its last column).
    ``FMTrainer.enter_model`` makes one from the public ``(w0, w, V)``;
    whoever scores many files with one model holds on to it."""
    w0: jax.Array
    T: jax.Array


def _live_mask(vals: np.ndarray) -> np.ndarray:
    """1.0 for a slot that holds a feature: a padded slot carries the
    value 0."""
    return (vals != 0).astype(np.float32)


class FMTrainer(DataParallelTrainer):
    """Data-parallel FM/FFM over a mesh.

    ``sparse_grads=True`` routes embedding gradients through the
    device-native sparse allreduce (the FFM workload of
    BASELINE.json configs[4]); default is the dense psum.
    ``sparse_capacity`` (replicated SGD step only) is the length of the
    list the step merges every shard's slot gradients into, so it bounds
    the DISTINCT features that all shards' batches of one step hold
    together; features beyond it would be dropped. The default, every
    slot of every shard (or ``n_features``), drops nothing.
    """

    TABLE_SHARDINGS = ("replicated", "sharded")

    def __init__(self, cfg: FMConfig, mesh=None, n_devices=None,
                 sparse_grads: bool = False,
                 sparse_capacity: int | None = None,
                 table_sharding: str = "replicated"):
        super().__init__(mesh=mesh, n_devices=n_devices)
        self.cfg = cfg
        self.sparse_grads = sparse_grads
        self.sparse_capacity = sparse_capacity
        if table_sharding not in self.TABLE_SHARDINGS:
            raise Mp4jError(
                f"table_sharding must be one of {self.TABLE_SHARDINGS}")
        if table_sharding == "sharded" and not sparse_grads:
            raise Mp4jError(
                "table_sharding='sharded' rides the sparse-gradient "
                "path; pass sparse_grads=True")
        if sparse_capacity is not None and (
                table_sharding == "sharded" or not sparse_grads):
            # only the replicated sparse step consumes it (the merged
            # list's length: a bound on the distinct FEATURES that all
            # shards' batches of a step touch together); anywhere else a
            # tuned capacity would be silently dropped
            raise Mp4jError(
                "sparse_capacity applies to the replicated sparse path "
                "only (sparse_grads=True, table_sharding='replicated'); "
                "the sharded step merges all of a member's slots and "
                "exchanges them in rounds of a fixed size, and the "
                "dense step has no capacity at all")
        self.table_sharding = table_sharding
        self._sharded = table_sharding == "sharded"
        # the sparse steps keep the table by feature, in blocks (the
        # sharded one a member's share of them), and update it in place
        self._blocks = sparse_grads
        self._adagrad = cfg.optimizer == "adagrad"
        if self._adagrad and (self._sharded or not self._blocks):
            raise Mp4jError(
                "optimizer='adagrad' runs on the replicated sparse step "
                "(sparse_grads=True, table_sharding='replicated'). The "
                "sharded step has the blocks and the exchange; what it "
                "lacks is the accumulators in the sharded block and the "
                "rule on the owner's side, where the members' lists "
                "would have to be merged first (SGD adds them one by "
                "one); the dense step a [n_rows, k] accumulator "
                "and a mask of the touched rows, or l2 is no longer lazy")
        if self._adagrad and sparse_capacity is not None:
            raise Mp4jError(
                "optimizer='adagrad' merges every slot of a step: its "
                "capacity is all of them, and a smaller sparse_capacity "
                "would drop features")
        # AdaGrad's accumulators after the last fit / fit_stream, in the
        # shapes of (w0, w, V); ``opt_state=`` hands them to the next
        self.opt_state_ = None
        # rounds of the sharded step's exchange that the last fit /
        # fit_stream ran, all steps together (one a step unless some
        # member held more than ``_EXCHANGE_CAP`` distinct features of
        # one owner); None on a replicated table
        self.exchange_rounds_ = None
        self._step = None
        self._step_key = None
        self._converters = None   # (widen, narrow), built on first use
        self._eval_fn = None
        self._pred_fn = None      # sharded serve (jit retraces by shape)
        # scoring (``predict`` on a replicated table): the block it reads,
        # the conversion into it where the step's is another, the
        # programs by (staged shape, rows a call), predict() calls so far
        self._score_cfg = _scoring_cfg(cfg)
        self._score_widen = None
        self._score_programs = {}
        self._score_jobs = 0
        self.eval_history_: list[float] = []

    @property
    def n_rows(self) -> int:
        """Embedding-table rows: |V| for FM, |V| * n_fields for FFM."""
        if self.cfg.model == "fm":
            return self.cfg.n_features
        return self.cfg.n_features * self.cfg.n_fields

    @property
    def n_features_padded(self) -> int:
        """Features padded to a multiple of the shard count: sharded mode
        gives every member the same number of WHOLE features, so that a
        boundary never cuts a feature's vectors (the padding features are
        never referenced: ids stay < n_features)."""
        n = self.n_shards
        return -(-self.cfg.n_features // n) * n

    @property
    def n_rows_padded(self) -> int:
        """Rows of the public table as sharded mode places it: those of
        ``n_features_padded`` features, n_rows_padded / n a member."""
        return self.n_features_padded * (self.n_rows // self.cfg.n_features)

    def init_params(self, seed: int = 0):
        rng = np.random.default_rng(seed)
        V = (self.cfg.init_scale
             * rng.standard_normal((self.n_rows, self.cfg.k))).astype(
                 np.float32)
        params = (jnp.zeros((), jnp.float32),
                  jnp.zeros((self.cfg.n_features,), jnp.float32),
                  jnp.asarray(V) if not self._sharded
                  else V)
        return self._stage_table(params)   # no-op unless sharded

    def full_table(self, params) -> np.ndarray:
        """The complete [n_rows, k] embedding table on the host,
        whatever the sharding (the serve/save shape)."""
        return self._to_host(params[2])[: self.n_rows]

    def _place_params(self, params):
        """Commit params to their exact step shardings (replicated
        scalars/linear weights; replicated or block-sharded table) so
        the first step call compiles the same program signature as
        every later one — see ``DataParallelTrainer._place_replicated``
        for the duplicate-compile failure this prevents."""
        if self._sharded:
            params = self._stage_table(params)
            return (*self._place_replicated(params[:2]), params[2])
        return self._place_replicated(params)

    def _stage_table(self, params):
        """Sharded mode: place a host/full-size table onto the mesh
        (padded to n_rows_padded, block-sharded). Already-staged params
        (from init_params or a previous step) pass through."""
        if not self._sharded:
            return params
        V = params[2]
        if (isinstance(V, jax.Array)
                and V.shape == (self.n_rows_padded, self.cfg.k)):
            return params
        V = np.asarray(V)[: self.n_rows]
        pad = self.n_rows_padded - self.n_rows
        if pad:
            V = np.pad(V, ((0, pad), (0, 0)))
        Vg = jax.make_array_from_callback(
            V.shape, self._row_sharding(), lambda idx: V[idx])
        return (jnp.asarray(params[0]), jnp.asarray(params[1]), Vg)

    # A conversion moves this many public rows at a time, so that what it
    # holds beside the two tables does not grow with them (AOT for v5e at
    # 2.62 GB: no temporaries; 0.33 GB a block with the fields outermost
    # in a block; the whole table reshaped at once: 83.7 GB, ISSUE 27).
    _CONVERT_ROWS = 5 * 2 ** 17

    def _state_avals(self):
        """Shapes of the step's ``(w0, T)`` replicated (AdaGrad:
        ``(w0, T, a0)``), or on a sharded table ``(w0, T, rounds)`` with
        the table's features cut over the mesh (compile proofs:
        check/checkaot.py, the AOT tests)."""
        cfg = self.cfg
        rep = NamedSharding(self.mesh, P())
        if self._sharded:
            return (jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
                    jax.ShapeDtypeStruct(
                        (self.n_features_padded, _block_width(cfg)),
                        jnp.float32, sharding=self._row_sharding()),
                    jax.ShapeDtypeStruct((), jnp.int32, sharding=rep))
        shapes = ((), (cfg.n_features, _block_width(cfg)))
        return tuple(
            jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rep)
            for shape in shapes + ((),) * self._adagrad)

    def _build_converters(self, scoring: bool = False):
        """``(widen, narrow)``: public ``(w0, w, V)`` -> the step's own
        ``(w0, T)`` and back, for the replicated sparse step
        (``scoring``: into and out of the parameters-only block that
        ``predict`` reads, :func:`_scoring_cfg`). Both return
        new buffers (the step donates its state, never the caller's
        arrays). The linear weights go into their column of the blocks
        (:func:`_weight_column`) and come back out of it. The FFM table
        goes ``_CONVERT_ROWS`` public rows at a time, the last block
        starting early and rewriting rows the one before placed.

        Under AdaGrad ``widen(params, opt)`` also takes the accumulators
        ``(a0, Gw, GV)`` in the shapes of ``(w0, w, V)``, or None for
        fresh ones (``adagrad_init`` beside every parameter, made in the
        blocks: no table of ones is held), and lays them into the
        blocks' second halves; ``narrow`` gives ``(params, opt)``.

        On a sharded table both run under ``shard_map``, every member on
        the features it owns: their rows of the public table
        ([n_rows_padded, k], cut over the mesh where ``_stage_table``
        cuts it) and their linear weights lie on the member that holds
        their blocks, so ``widen`` holds no collective at all and
        ``narrow`` one, the gathering of the linear weights into the
        replicated [n_features] vector that the public form keeps;
        nothing of the table crosses. The state is ``(w0, T, rounds)``,
        ``rounds`` from 0."""
        cfg = self._score_cfg if scoring else self.cfg
        adagrad = cfg.optimizer == "adagrad"
        sharded = self._sharded and not scoring
        k = cfg.k
        nf = self.n_rows // cfg.n_features  # vectors a block: n_fields, or 1
        # features a program converts: all, or a member's share
        F = (self.n_features_padded // self.n_shards if sharded
             else cfg.n_features)
        width, stride = _block_width(cfg), _block_stride(cfg)
        hw, wcol = _weights_width(cfg), _weight_column(cfg)
        B = max(1, min(F, self._CONVERT_ROWS // nf))
        if B >= 128:
            B -= B % 128        # starts on whole lane tiles of the public table
        n_blocks = -(-F // B)
        if adagrad:
            fresh = jnp.asarray(np.float32(cfg.adagrad_init)
                                * _field_columns(cfg).max(axis=0))

        def blockwise(src, out, move):
            def body(i, out):
                return move(src, out, jnp.minimum(i * B, F - B))
            return lax.fori_loop(0, n_blocks, body, out)

        def half_in(pair, f0):
            """[B, hw] of features f0.. from one public ``(w, V)``."""
            if pair is None:
                return jnp.broadcast_to(fresh, (B, hw))
            w, V = pair
            rows = lax.dynamic_slice(V, (f0 * nf, 0), (B * nf, k))
            runs = jnp.pad(rows.T.reshape(k, B, nf),
                           ((0, 0), (0, 0), (0, stride - nf)))
            blk = jnp.pad(runs.transpose(1, 0, 2).reshape(B, k * stride),
                          ((0, 0), (0, hw - k * stride)))
            return lax.dynamic_update_slice(
                blk, lax.dynamic_slice(w, (f0,), (B,))[:, None], (0, wcol))

        def half_out(blk, pair, f0):
            """One public ``(w, V)`` with features f0.. taken from the
            [B, hw] half ``blk``."""
            w, V = pair
            runs = blk[:, :k * stride].reshape(B, k, stride)[:, :, :nf]
            return (lax.dynamic_update_slice(w, blk[:, wcol], (f0,)),
                    lax.dynamic_update_slice(
                        V, runs.transpose(1, 0, 2).reshape(k, B * nf).T,
                        (f0 * nf, 0)))

        def to_blocks(pairs, T, f0):
            halves = [half_in(pair, f0) for pair in pairs]
            blk = (halves[0] if len(halves) == 1
                   else jnp.concatenate(halves, axis=1))
            return lax.dynamic_update_slice(T, blk, (f0, 0))

        def to_rows(T, pairs, f0):
            blk = lax.dynamic_slice(T, (f0, 0), (B, width))
            return tuple(half_out(blk[:, h * hw:(h + 1) * hw], pair, f0)
                         for h, pair in enumerate(pairs))

        def widen(params, opt=None):
            w0, w, V = params
            if nf == 1:
                return jnp.copy(w0), jnp.concatenate([V, w[:, None]], axis=1)
            pairs = ((w, V),)
            if adagrad:
                pairs += (None if opt is None else opt[1:],)
            T = blockwise(pairs, jnp.zeros((F, width), V.dtype), to_blocks)
            if not adagrad:
                return jnp.copy(w0), T
            a0 = (jnp.full((), cfg.adagrad_init, w0.dtype) if opt is None
                  else jnp.copy(opt[0]))
            return jnp.copy(w0), T, a0

        def narrow(state):
            w0, T = state[:2]
            if nf == 1:
                return jnp.copy(w0), T[:, wcol], T[:, :k]
            empty = (jnp.zeros((F,), T.dtype), jnp.zeros((F * nf, k), T.dtype))
            pairs = blockwise(T, (empty,) * (1 + adagrad), to_rows)
            params = (jnp.copy(w0), *pairs[0])
            if not adagrad:
                return params
            return params, (jnp.copy(state[2]), *pairs[1])

        # committed like the placed params, so that the first step call
        # compiles the program every later one runs
        rep = NamedSharding(self.mesh, P())
        if sharded:
            axes, rows = self.axes, self._row_sharding()
            n_features = cfg.n_features
            on_shards = partial(jax.shard_map, mesh=self.mesh,
                                check_vma=False)

            def widen_shards(params):
                w0, w, V = params

                def mine(w, V):     # the bias takes no part in a table
                    f0 = collectives.flat_index(axes) * F
                    return widen((np.float32(0),
                                  lax.dynamic_slice(w, (f0,), (F,)), V))[1]

                T = on_shards(mine, in_specs=(P(), P(axes)),
                              out_specs=P(axes))(
                    jnp.pad(w, (0, self.n_features_padded - n_features)), V)
                return jnp.copy(w0), T, jnp.zeros((), jnp.int32)

            def narrow_shards(state):
                w0, T = state[:2]
                w, V = on_shards(lambda T: narrow((np.float32(0), T))[1:],
                                 in_specs=P(axes),
                                 out_specs=(P(axes), P(axes)))(T)
                return jnp.copy(w0), w[:n_features], V

            with spans.span("mp4j.step.build", key="table_converters",
                            block_features=B, table_sharding="sharded"):
                return (jax.jit(widen_shards, out_shardings=(rep, rows, rep)),
                        jax.jit(narrow_shards,
                                out_shardings=(rep, rep, rows)))
        with spans.span("mp4j.step.build", key="table_converters",
                        block_features=B):
            return (jax.jit(widen, out_shardings=rep),
                    jax.jit(narrow, out_shardings=rep))

    def _enter(self, params, opt_state=None):
        """Public params -> the state the step carries. The sparse steps
        get their own ``(w0, T)`` (``widen``; a sharded table cut over
        the mesh, with the count of exchange rounds beside it), under
        AdaGrad with the accumulators ``opt_state`` in it (None: fresh
        ones); the dense step takes the placed params as they are."""
        with spans.span("mp4j.stream.widen"):
            if (not self._sharded
                    and params[2].shape != (self.n_rows, self.cfg.k)):
                raise Mp4jError(
                    f"the embedding table must be [n_rows={self.n_rows}, "
                    f"k={self.cfg.k}], got {params[2].shape}")
            if opt_state is not None:
                if not self._adagrad:
                    raise Mp4jError(
                        "opt_state is AdaGrad's accumulators; this "
                        f"trainer's optimizer is {self.cfg.optimizer!r}, "
                        "which carries none")
                shapes = [tuple(np.shape(a)) for a in opt_state]
                if shapes != [tuple(np.shape(p)) for p in params]:
                    raise Mp4jError(
                        "opt_state must have the shapes of (w0, w, V), "
                        f"got {shapes}")
                opt_state = self._place_replicated(tuple(opt_state))
            # the accumulators of the call before are the caller's now
            self.opt_state_ = None
            params = self._place_params(params)
            if not self._blocks:
                return params
            if self._converters is None:
                self._converters = self._build_converters()
            widen = self._converters[0]
            return jax.block_until_ready(
                widen(params, opt_state) if self._adagrad else widen(params))

    def _leave(self, state):
        """The step's state -> public params, in new buffers (``state``
        stays valid: the snapshot of an early-stopping round is taken
        this way too). AdaGrad's accumulators come out beside them, into
        ``opt_state_``; the sharded step's count of exchange rounds into
        ``exchange_rounds_`` and the span's arguments."""
        said = {}
        if self._sharded:
            # replicated: any of this process's copies
            self.exchange_rounds_ = int(state[2].addressable_data(0))
            said = dict(exchange_rounds=self.exchange_rounds_)
        with spans.span("mp4j.stream.narrow", **said):
            if not self._blocks:
                return state
            out = jax.block_until_ready(self._converters[1](state))
            if self._adagrad:
                out, self.opt_state_ = out
            return out

    def save_params(self, path: str, params) -> None:
        """Persist with the table in its portable [n_rows, k] shape
        (a sharded table is gathered + unpadded first, so the file is
        loadable at any shard count)."""
        if self._sharded:
            params = (self._to_host(params[0]),
                      self._to_host(params[1]), self.full_table(params))
        super().save_params(path, params)

    def _build_step(self, per_shard_slots: int):
        cfg = self.cfg
        axes = self.axes
        dspec = P(axes)
        if self._sharded:
            cap = _exchange_cap(per_shard_slots)
            step_fn = partial(train_step_sparse_sharded, cfg=cfg,
                              n=self.n_shards, cap=cap, axis_name=axes)
            # the table's features cut over the mesh; the bias and the
            # count of rounds are every member's alike (psum, pmax),
            # which VMA checking cannot prove of pcast values
            pspec = (P(), dspec, P())

            @partial(jax.shard_map, mesh=self.mesh, check_vma=False,
                     in_specs=(pspec,) + (dspec,) * 6,
                     out_specs=(pspec, P()))
            def step(params, feats, fields, vals, mask, y, sw):
                batch = (feats[0], fields[0], vals[0], mask[0], y[0],
                         sw[0])
                return step_fn(params, batch)

            # descriptors: the slots a member sorts; what an owner gathers
            # and scatter-adds goes with the distinct features asked of it
            with spans.span("mp4j.step.build", key=per_shard_slots,
                            table_sharding="sharded", table_form="blocks",
                            owners=self.n_shards, exchange_cap=cap,
                            exchange_tile=_shard_tile(cap),
                            descriptors=per_shard_slots, index_streams=1,
                            block_width=_block_width(cfg),
                            **_select_build_args(cfg)):
                # the state is the trainer's own (``_enter``): donated
                return jax.jit(step, donate_argnums=0)
        build_args = {}
        jit_args = {}
        if self.sparse_grads:
            cap = self.sparse_capacity
            if cap is None:
                # global unique touched features can't exceed total
                # slots this step, nor the vocabulary (the caller's
                # slots: :func:`_merge_slots` drops a step's dead rows')
                cap = min(cfg.n_features, per_shard_slots * self.n_shards)
            dead = _dead_rows(per_shard_slots)
            step_fn = partial(
                train_step_adagrad if self._adagrad else train_step_sparse,
                cfg=cfg, capacity=cap, axis_name=axes)
            # the state is the trainer's own (``_enter``): donated, the
            # table is scattered into where it rests
            jit_args = dict(donate_argnums=0)
            # index_streams: gather/scatter-add pairs the step issues a
            # (sample, feature); the weights ride in the blocks
            # update_tile: the update loop's tile; update_tiles: its
            # trips for a chunk whose slots are all distinct
            tile = _update_tile(cap)
            build_args = dict(table_form="blocks",
                              descriptors=(per_shard_slots
                                           + dead * cfg.max_nnz),
                              dead_rows=dead,
                              index_streams=1, optimizer=cfg.optimizer,
                              block_width=_block_width(cfg), capacity=cap,
                              update_tile=tile,
                              update_tiles=-(-cap // tile),
                              **_select_build_args(cfg))
            # params are pcast to varying but returned under replicated
            # P() out_specs (every shard computes the identical update
            # from the all-gathered slots + psum'd scalars), which VMA
            # checking cannot prove — same waiver class as the sparse
            # path in comm.tpu_comm (correctness is covered by the
            # dense-vs-sparse differential test)
            check_vma = False
        else:
            step_fn = partial(train_step_dense, cfg=cfg, axis_name=axes)
            check_vma = True

        @partial(jax.shard_map, mesh=self.mesh, check_vma=check_vma,
                 in_specs=(P(),) + (dspec,) * 6, out_specs=(P(), P()))
        def step(params, feats, fields, vals, mask, y, sw):
            batch = (feats[0], fields[0], vals[0], mask[0], y[0], sw[0])
            return step_fn(params, batch)

        with spans.span("mp4j.step.build", key=per_shard_slots,
                        **build_args):
            return jax.jit(step, **jit_args)

    def _check_ids(self, feats: np.ndarray, fields: np.ndarray):
        """Shared id-range validation for fit and predict inputs (JAX
        gathers clamp out-of-range indices silently, so bad ids must be
        rejected on the host)."""
        if (feats.min(initial=0) < 0
                or feats.max(initial=0) >= self.cfg.n_features):
            raise Mp4jError("feature id out of range")
        if self.cfg.model == "ffm" and (
                fields.min(initial=0) < 0
                or fields.max(initial=0) >= self.cfg.n_fields):
            raise Mp4jError("field id out of range")

    def shard_data(self, feats, fields, vals, y, sample_weight=None):
        """Pad + shard padded-sparse instances.

        feats/fields: [N, K] int (K <= max_nnz; padded slots = any id
        with value 0); vals: [N, K] float; y: [N]. ``sample_weight``
        ([N] f32, optional — ytk-learn's instance weights) scales each
        example's loss/gradient contribution (the step normalizes by
        the weight sum, so integer weights train exactly like row
        duplication) and composes with the padding zeros."""
        y = np.asarray(y, np.float32)
        feats, fields, vals = self._stage_instances(feats, fields, vals)
        mask = _live_mask(vals)
        N = feats.shape[0]
        (feats, fields, vals, mask, y), per, sw = self._pad_rows(
            [feats, fields, vals, mask, y])
        sw[:N] *= self._stage_weights(sample_weight, N)
        put = lambda a: self._put_sharded(a, per)  # noqa: E731
        return (put(feats), put(fields), put(vals), put(mask), put(y),
                put(sw))

    def fit(self, feats, fields, vals, y, n_steps: int = 100, params=None,
            seed: int = 0, eval_set=None,
            early_stopping_rounds: int | None = None,
            sample_weight=None, opt_state=None):
        """Full-batch training; returns (params, losses).

        ``eval_set=(feats_va, fields_va, vals_va, y_va)`` evaluates the
        held-out loss after every step (history in
        ``self.eval_history_``); ``early_stopping_rounds=k`` stops after
        k non-improving steps and returns the best round's params;
        ``sample_weight`` ([N]) weights each example's loss/gradient
        (integer weights == row duplication). With
        ``optimizer="adagrad"`` the accumulators are left in
        ``self.opt_state_`` (those of the returned params), and
        ``opt_state=`` takes them back: n steps and n more with the
        state handed over are 2n steps, bit for bit.
        """
        if early_stopping_rounds is not None and eval_set is None:
            raise Mp4jError("early_stopping_rounds requires an eval_set")
        sharded = self.shard_data(feats, fields, vals, y,
                                  sample_weight=sample_weight)
        # the jitted step bakes in the sparse capacity, which depends on
        # the per-shard batch size — rebuild when that changes (a stale
        # smaller capacity would silently drop gradient rows)
        per_shard_slots = int(sharded[0].shape[1]) * self.cfg.max_nnz
        if self._step is None or self._step_key != per_shard_slots:
            self._step = self._build_step(per_shard_slots)
            self._step_key = per_shard_slots
        if params is None:
            params = self.init_params(seed)
        state = self._enter(params, opt_state)
        del params, opt_state
        va = None
        if eval_set is not None:
            va = self._prep_eval(*eval_set)
        stopper = EarlyStopper(early_stopping_rounds)
        self.eval_history_ = stopper.history
        losses = []
        best = None             # early stopping: the best round's params
        stopped = False
        for i in range(n_steps):
            state, loss = self._step(state, *sharded)
            # bound in-flight programs; see models/linear.py fit()
            loss = jax.block_until_ready(loss)
            losses.append(loss)
            if va is None:
                continue
            stopped = stopper.update(self._eval_loss(
                state, va, _score_blocks if self._blocks else _score), i)
            if early_stopping_rounds is not None and stopper.best_round == i:
                # the next step may donate ``state``: the best round's
                # params are taken out now, in buffers of their own
                best = self._leave(state)
            if stopped:
                break
        if stopped and best is not None:
            params, losses = best, losses[:stopper.best_round + 1]
        else:
            params = self._leave(state)
        return params, np.asarray(jax.device_get(losses))

    def fit_stream(self, batches, params=None, seed: int = 0,
                   batch_rows: int | None = None,
                   max_in_flight: int = 2, opt_state=None):
        """Chunked (out-of-core) training for data that cannot be staged
        in memory — the Criteo-1TB shape of configs[4], where
        ytk-learn consumes streamed libsvm-format text. ``batches`` is
        any iterator/generator of ``(feats, fields, vals, y)``
        minibatches (``utils.libsvm.read_libsvm`` streams them from
        disk) — or 5-tuples with per-chunk instance weights appended;
        one optimizer step runs per chunk.

        Every chunk is padded to ``batch_rows`` total rows (default:
        the first chunk's size rounded up to the shard count) with
        zero-weight rows, so ONE jitted program serves the whole
        stream — drifting chunk sizes would otherwise recompile per
        distinct size. A chunk larger than ``batch_rows`` raises.
        Feeding the full dataset as a single chunk E times is
        numerically identical to ``fit(n_steps=E)`` (tested in
        tests/test_fm.py). Returns (params, per-chunk losses).

        The pipeline is DOUBLE-BUFFERED via the shared
        :meth:`DataParallelTrainer._stream_fit` loop: step k is
        dispatched asynchronously and chunk k+1 is parsed/padded/staged
        while the device runs it; losses are fetched once at the end.
        At most ``max_in_flight`` steps stay in flight, bounding device
        memory at ~max_in_flight staged batches. With
        ``sparse_grads=True`` the step carries the table by feature,
        the linear weights inside it, and updates it in place: it is
        converted once here (``mp4j.stream.widen``) and once before the
        return (``mp4j.stream.narrow``); the table passed in is left as
        it was, and the one returned is [n_rows, k] (a sharded table:
        [n_rows_padded, k] cut over the mesh, every member converting
        the features it owns; ``exchange_rounds_`` says how many rounds
        the steps' exchanges ran). AdaGrad's accumulators make the same two trips
        inside the same blocks: ``opt_state=`` takes those of an earlier
        call (``self.opt_state_``, the shapes of ``(w0, w, V)``; None
        starts them at ``adagrad_init``), so that a stream of 2n chunks
        and two calls of n give the same bits. ``max_in_flight=0``
        reproduces the fully serialized round-4 behavior (the
        overlap's gain was not resolved above noise on the previous
        installation, 2026-07; see ROADMAP S6)."""
        if params is None:
            params = self.init_params(seed)
        state = [self._enter(params, opt_state)]
        # the caller's table is theirs: not donated, and not kept here
        del params, opt_state

        def dispatch(staged):
            sharded, per_shard_slots = staged
            # (re)build on padded-shape change: a stale smaller
            # capacity would silently drop gradient rows
            if self._step is None or self._step_key != per_shard_slots:
                self._step = self._build_step(per_shard_slots)
                self._step_key = per_shard_slots
            state[0], loss = self._step(state[0], *sharded)
            return loss

        losses = self._stream_fit(batches, self._stage_stream_chunk,
                                  dispatch, batch_rows, max_in_flight)
        return self._leave(state.pop()), losses

    def _stage_stream_chunk(self, chunk, batch_rows: int | None):
        """Host half of one stream step: validate, pad to ``batch_rows``
        (resolving it from the first chunk), and start the async
        device placement. Returns ((sharded..., per_shard_slots),
        batch_rows)."""
        feats, fields, vals, y = chunk[:4]
        weights = chunk[4] if len(chunk) > 4 else None
        y = np.asarray(y, np.float32)
        feats, fields, vals = self._stage_instances(feats, fields, vals)
        mask = _live_mask(vals)
        if batch_rows is None:
            batch_rows = (-(-feats.shape[0] // self.n_shards)
                          * self.n_shards)
        N = feats.shape[0]
        (feats, fields, vals, mask, y), sw, per = self._pad_stream_rows(
            [feats, fields, vals, mask, y], batch_rows)
        sw[:N] *= self._stage_weights(weights, N)
        sharded = tuple(self._put_sharded(a, per)
                        for a in (feats, fields, vals, mask, y, sw))
        return (sharded, per * self.cfg.max_nnz), batch_rows

    def _stage_instances(self, feats, fields, vals, check_ids: bool = True):
        """The one staging path for padded-sparse instances: validate id
        ranges and pad the slot axis to max_nnz (padded slots carry value
        0, which is what :func:`_live_mask` reads). Shared by shard_data,
        predict and eval so the padding convention cannot drift between
        them. Arrays that are int32 / float32 and ``max_nnz`` wide
        already come back as they are, not copied. ``check_ids=False``
        leaves the ids' ranges to the caller (``predict`` validates a
        staging chunk at a time, :meth:`_check_ids`)."""
        feats = np.asarray(feats, np.int32)
        fields = np.asarray(fields, np.int32)
        vals = np.asarray(vals, np.float32)
        if feats.shape != fields.shape or feats.shape != vals.shape:
            raise Mp4jError(
                "feats, fields and vals must be [N, K] alike, got "
                f"{feats.shape}, {fields.shape}, {vals.shape}")
        if feats.ndim != 2 or feats.shape[1] > self.cfg.max_nnz:
            raise Mp4jError(
                f"feats must be [N, K<={self.cfg.max_nnz}], got {feats.shape}")
        if check_ids:
            self._check_ids(feats, fields)
        padK = self.cfg.max_nnz - feats.shape[1]
        if padK:
            zK = ((0, 0), (0, padK))
            feats, fields, vals = (np.pad(feats, zK), np.pad(fields, zK),
                                   np.pad(vals, zK))
        return feats, fields, vals

    def _prep_eval(self, feats, fields, vals, y):
        """Pad + stage a held-out batch once for per-step evaluation."""
        feats, fields, vals = self._stage_instances(feats, fields, vals)
        return (jnp.asarray(feats), jnp.asarray(fields),
                jnp.asarray(vals), jnp.asarray(_live_mask(vals)),
                jnp.asarray(np.asarray(y, np.float32)))

    def _eval_loss(self, params, va, score=_score) -> float:
        """Held-out loss of public ``params``, or with
        ``score=_score_blocks`` of the replicated sparse step's state."""
        if self._eval_fn is None:
            cfg = self.cfg

            @partial(jax.jit, static_argnums=0)
            def run(score, params, feats, fields, vals, mask, y):
                z = score(params, feats, fields, vals, mask, cfg)
                return jnp.mean(per_example_loss(z, y, cfg.loss))

            self._eval_fn = run
        # params may span non-addressable devices on multi-process
        # meshes; a plain local jit cannot consume those directly
        return float(self._eval_fn(score, self._local_values(params), *va))

    def _build_sharded_predict(self):
        """Serve-side shard_map program: owner-routed row fetch from
        the SHARDED table — the full [n_rows, k] replica is never
        materialized anywhere, which is the point of sharding a
        Criteo-scale vocabulary in the first place."""
        from ytk_mp4j_tpu.ops.collectives import flat_index

        cfg = self.cfg
        axes = self.axes
        dspec = P(axes)

        @partial(jax.shard_map, mesh=self.mesh, check_vma=False,
                 in_specs=((P(), P(), dspec),) + (dspec,) * 4,
                 out_specs=dspec)
        def run(params, feats, fields, vals, mask):
            w0, w, Vs = params
            f0, fl0 = feats[0], fields[0]
            rows = _slot_rows(f0, fl0, cfg)
            E_flat, _, _ = _fetch_rows_sharded(
                Vs, rows.reshape(-1).astype(jnp.int32),
                flat_index(axes), axes)
            E = _by_component(
                E_flat.reshape(rows.shape + (Vs.shape[1],)), cfg)
            z = _score_from_slots(w0, w[f0], E, vals[0] * mask[0], cfg)
            if cfg.loss == "logistic":
                z = jax.nn.sigmoid(z)
            return z[None]

        return jax.jit(run)

    def enter_model(self, params) -> EnteredModel:
        """Public ``(w0, w, V)`` -> the model as ``predict`` scores it,
        on the trainer's mesh: the bias and the table by feature, a
        parameters-only block a row whatever ``cfg.optimizer`` is
        ([n_features, 256] at 39 fields x 4; accumulators never enter).
        ``predict`` takes the result in place of the params, so a caller
        who scores many files with one model converts it once (TPU v5
        lite, the 2.62 GB table: 0.06 s of device time, and both tables
        side by side while it runs). New buffers: the caller's arrays
        stay as they were. Span ``mp4j.ffm.score.enter``."""
        if self._sharded:
            raise Mp4jError(
                "a sharded table is scored where it rests, a row a slot "
                "pair (predict takes the params themselves); a model "
                "enters for the replicated table only")
        w0, w, V = params
        cfg = self.cfg
        shapes = [tuple(np.shape(a)) for a in (w0, w, V)]
        if shapes != [(), (cfg.n_features,), (self.n_rows, cfg.k)]:
            raise Mp4jError(
                f"a model is (w0 [], w [n_features={cfg.n_features}], "
                f"V [n_rows={self.n_rows}, k={cfg.k}]), got {shapes}")
        with spans.span("mp4j.ffm.score.enter"):
            if self._blocks and not self._adagrad:
                # the SGD step's own block is the one scoring reads
                if self._converters is None:
                    self._converters = self._build_converters()
                widen = self._converters[0]
            else:
                if self._score_widen is None:
                    self._score_widen = self._build_converters(
                        scoring=True)[0]
                widen = self._score_widen
            return EnteredModel(*jax.block_until_ready(
                widen(self._place_replicated((w0, w, V)))))

    def _build_score(self, shape, rows: int):
        """The scoring program for pieces of instances whose ids, fields
        and values each cross as ``shape`` ([n_shards, M, 128] words, or
        [n_shards, rows, max_nnz]), of which it scores the last ``rows``
        rows of every shard (all, but for a last piece that began
        early): :func:`score_rows` under ``shard_map``, rows sharded,
        the entered model replicated, no collective. It takes (ids,
        fields, values, model, probabilities [n_shards, rows a shard],
        first row) and returns the probabilities, donated, with those
        rows filled in, and the piece's first word, which is there when
        the device has had the piece. The three are put into rows of
        ``max_nnz`` inside the program, under the scope
        ``stage.place``."""
        cfg = self._score_cfg
        axes = self.axes
        K = cfg.max_nnz
        held = int(np.prod(shape[1:])) // K

        @partial(jax.shard_map, mesh=self.mesh, check_vma=False,
                 in_specs=(P(axes),) * 3 + (P(), P(axes), P()),
                 out_specs=(P(axes), P(axes)))
        def score(feats, fields, vals, model, out, start):
            # the device's side of the hand-over, by name in a trace
            with jax.named_scope("stage.place"):
                marker = feats.reshape(-1)[:1]
                piece = [a.reshape(held, K) for a in (feats, fields, vals)]
            return score_rows(*piece, model, out[0], start, cfg,
                              skip=held - rows)[None], marker

        tile = _score_tile(rows)
        with spans.span("mp4j.step.build", key="ffm_score", rows=rows,
                        tile=tile, tiles=-(-rows // tile),
                        block_width=_block_width(cfg),
                        **_select_build_args(cfg)):
            return jax.jit(score, donate_argnums=4)

    def predict(self, params, feats, fields, vals):
        """What the model says of padded-sparse instances: a probability
        a row under the logistic loss, else the score; [N] f32, in the
        rows' order. ``params`` is the public ``(w0, w, V)``, or the
        :class:`EnteredModel` that ``enter_model`` made of it (a
        replicated table; whoever scores more than one file with a model
        enters it once).

        On a replicated table the instances are sharded as ``fit``'s
        are, rows padded to whole shards over the trainer's mesh, and
        cross in pieces of rows as the host holds them, ids, fields and
        values each on its own (``_array_cuts``: arrays that are int32 /
        float32 and full width are not copied on the host; ids are
        validated a piece at a time, while the pieces before are
        scored); one jitted ``shard_map`` program (:func:`score_rows`)
        takes the three as they crossed and scores the piece while the
        next ones cross, a tile of rows at a time and one gather
        descriptor a (row, slot). No table of instances is built: a
        piece is let go when its turn is over. The probabilities are
        written into one donated array and fetched once. The programs
        are kept by (piece shape, rows a shard, rows a call): a repeated
        ``predict`` of the same shape builds nothing. Spans
        ``mp4j.ffm.score.stage`` / ``dispatch`` / ``fetch``.

        A sharded table keeps its own program
        (``_build_sharded_predict``, the row form: a row a slot pair,
        every member's requests gathered by every owner; ROADMAP R1)."""
        if self._sharded:
            feats, fields, vals = self._stage_instances(feats, fields, vals)
            params = self._stage_table(params)
            N = feats.shape[0]
            (f, fl, v, m), per, _ = self._pad_rows(
                [feats, fields, vals, _live_mask(vals)], weights=False)
            if self._pred_fn is None:
                self._pred_fn = self._build_sharded_predict()
            staged = [self._put_sharded(a, per) for a in (f, fl, v, m)]
            # _to_host, not np.asarray: on multi-process (global)
            # meshes the output spans non-addressable devices, so the
            # fetch is a collective process_allgather — every process
            # must call predict together there
            out = self._to_host(self._pred_fn(params, *staged))
            return out.reshape(-1)[:N]
        model = (params if isinstance(params, EnteredModel)
                 else self.enter_model(params))
        feats, fields, vals = self._stage_instances(feats, fields, vals,
                                                    check_ids=False)
        N = feats.shape[0]
        if not N:
            return np.zeros(0, np.float32)
        job, self._score_jobs = self._score_jobs, self._score_jobs + 1
        probs = None                # the device's, as last returned
        scored = 0                  # rows of a shard scored so far
        with spans.span("mp4j.ffm.score.stage", job=job, rows=N):
            arrays, per, _ = self._pad_rows([feats, fields, vals],
                                            weights=False)
            parts = tuple(a.reshape(self.n_shards, per, -1) for a in arrays)
            with spans.span("mp4j.put_sharded",
                            bytes=sum(a.nbytes for a in parts)):
                for _, piece, _, start, stop, turns in self._crossed(
                        self._array_cuts(parts)):
                    # the last piece starts early, over rows that the
                    # one before it brought: those are done
                    start, scored = max(start, scored), stop
                    # on its way already, and not scored if an id is
                    # out of range
                    self._check_ids(parts[0][:, start:stop],
                                    parts[1][:, start:stop])
                    key = (piece[0].shape, per, stop - start)
                    program = self._score_programs.get(key)
                    if program is None:
                        program = self._score_programs[key] = \
                            self._build_score(key[0], key[2])
                    with spans.span("mp4j.ffm.score.dispatch", job=job,
                                    rows=stop - start, start=start):
                        if probs is None:
                            probs = jnp.zeros(
                                (self.n_shards, per), jnp.float32,
                                device=self._row_sharding())
                        probs, done = program(*piece, tuple(model), probs,
                                              np.int32(start))
                    turns.append(done)
                    del piece
        with spans.span("mp4j.ffm.score.fetch", job=job):
            # _to_host: a collective fetch on multi-process meshes, where
            # every process calls predict together
            return self._to_host(probs).reshape(-1)[:N]


# ----------------------------------------------------------------------
# serve adapter (ISSUE 19): the pull-mode sharded entry point
# ----------------------------------------------------------------------
class FMServable:
    """Row-pull serve adapter for a trained FM / FFM model — the host
    twin of :meth:`FMTrainer._build_sharded_predict` (the AOT
    ``ffm/sharded_serve`` program): the full table is never
    materialized on the frontend; a batch pulls exactly the rows it
    touches, owner-routed by ``row_id % size`` over the columnar map
    plane, and hot rows come out of the frontend cache instead.

    A pull ROW is one feature's whole serve payload: ``[w[f]]`` +
    its embedding row(s) — ``1 + k`` floats for FM, ``1 +
    n_fields * k`` for FFM (feature f's rows against every field,
    flattened). Scoring is per example in slot order, so batched and
    sequential serve predictions are bitwise identical by
    construction.
    """

    kind = "pull"

    def __init__(self, params, cfg: FMConfig):
        w0, w, V = params
        self.cfg = cfg
        self.family = cfg.model
        self._w0 = float(jax.device_get(w0))
        self._w = np.asarray(jax.device_get(w), np.float32)
        V = np.asarray(jax.device_get(V), np.float32)
        nf = cfg.n_fields if cfg.model == "ffm" else 1
        # [n_features, nf * k]: feature f's embedding payload
        self._E = np.ascontiguousarray(
            V[:cfg.n_features * nf].reshape(cfg.n_features,
                                            nf * cfg.k))
        self.n_rows = cfg.n_features
        self.row_width = 1 + nf * cfg.k
        self.resp_width = 1

    def row_ids(self, req) -> np.ndarray:
        """Unique features an instance's ACTIVE slots touch."""
        feats, _fields, vals = req
        return np.unique(np.asarray(feats, np.int64)[
            np.asarray(vals, np.float32) != 0])

    def rows(self, ids) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        return np.concatenate(
            [self._w[ids, None], self._E[ids]],
            axis=1).astype(np.float64)

    def predict_sharded(self, reqs, rowmap) -> list:
        out = []
        k = self.cfg.k
        zero = np.zeros(self.row_width, np.float32)
        for feats, fields, vals in reqs:
            feats = np.asarray(feats, np.int64)
            fields = np.asarray(fields, np.int32)
            vals = np.asarray(vals, np.float32)
            act = np.flatnonzero(vals != 0)
            rows = [rowmap.get(int(feats[a]))
                    for a in act]
            rows = [zero if r is None else r.astype(np.float32)
                    for r in rows]
            z = np.float32(self._w0)
            for r, a in zip(rows, act):
                z += r[0] * vals[a]
            if self.cfg.model == "fm":
                # 0.5 * ((sum_a v_a x_a)^2 - sum_a (v_a x_a)^2) over k
                s = np.zeros(k, np.float32)
                ss = np.zeros(k, np.float32)
                for r, a in zip(rows, act):
                    ex = r[1:] * vals[a]
                    s += ex
                    ss += ex * ex
                z += np.float32(0.5) * np.sum(s * s - ss)
            else:
                # FFM: sum_{a<b} <E[f_a, fl_b], E[f_b, fl_a]> x_a x_b
                for i in range(len(act)):
                    for j in range(i + 1, len(act)):
                        a, b = act[i], act[j]
                        ra = rows[i][1 + fields[b] * k:
                                     1 + (fields[b] + 1) * k]
                        rb = rows[j][1 + fields[a] * k:
                                     1 + (fields[a] + 1) * k]
                        z += np.dot(ra, rb) * vals[a] * vals[b]
            out.append(_serve_link(z, self.cfg.loss))
        return out


def _serve_link(z, loss: str) -> np.ndarray:
    """Overflow-safe host link on a scalar margin."""
    z = float(z)
    if loss == "logistic":
        if z >= 0:
            p = 1.0 / (1.0 + np.exp(-z))
        else:
            e = np.exp(z)
            p = e / (1.0 + e)
        return np.asarray([p], np.float64)
    return np.asarray([z], np.float64)


def servable(params, cfg: FMConfig) -> FMServable:
    """The serve plane's per-family entry point (ISSUE 19) — covers
    both ``model="fm"`` and ``model="ffm"``."""
    return FMServable(params, cfg)
