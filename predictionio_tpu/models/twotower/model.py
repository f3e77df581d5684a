"""Two-tower retrieval network + sharded training step.

TPU-first design (no reference counterpart — this is the deep-retrieval
workload from BASELINE.json):

  - Towers: id embedding -> MLP -> L2-normalized output embedding; bf16
    matmuls on the MXU, f32 accumulation for the loss.
  - Loss: in-batch sampled softmax with temperature — logits are one
    [B, B] matmul of user x item embeddings, the canonical retrieval loss.
  - Sharding: batch axis over the mesh's ``data`` axis; the two embedding
    tables are sharded over the ``model`` axis along the vocab dimension
    (they dominate memory at MovieLens-20M scale); dense layers replicated.
    XLA/GSPMD inserts the all-gathers for embedding lookups and the psum for
    the data-parallel gradient — no hand-written collectives.
  - The train step is one jitted function with donated optimizer state.
  - Optional sequence encoder (``history_len > 0``): the user tower fuses a
    causal self-attention encoding of the user's recent item history into
    the id embedding. Attention runs through ``ops.attention.fused_attention``
    — the pallas TPU kernel on TPU, the jnp reference elsewhere. Histories
    are chronological with -1 padding at the END, so causal masking already
    keeps pad keys invisible to real positions and pooling masks the rest;
    no separate key-padding mask is needed.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class TwoTowerConfig:
    n_users: int
    n_items: int
    embed_dim: int = 64
    hidden: tuple[int, ...] = (128,)
    out_dim: int = 32
    temperature: float = 0.05
    learning_rate: float = 1e-3
    batch_size: int = 4096
    epochs: int = 5
    seed: int = 0
    # mid-training checkpoint/resume (the reference has no step-level
    # checkpointing, SURVEY.md section 5 — `pio train` is all-or-nothing;
    # this closes that gap). Directory for epoch checkpoints; None disables.
    checkpoint_dir: str | None = None
    checkpoint_every: int = 1  # epochs between checkpoints
    resume: bool = True  # continue from the newest checkpoint if present
    # sequence encoder: 0 disables; > 0 = length of the per-user item
    # history consumed by causal self-attention in the user tower (on the
    # chip, 1024 or more must be a multiple of 256: fused_attention)
    history_len: int = 0
    n_heads: int = 2
    # sequence/context parallelism for the history encoder: when True and a
    # mesh is passed to ``train_two_tower``, the encoder's attention shards
    # the history sequence over the mesh's ``model`` axis (ring attention's
    # K/V ppermute or Ulysses' all_to_alls over ICI) composed with the
    # batch's ``data``-axis sharding — dp x sp on one 2-D mesh. This is how
    # histories longer than one device's memory train; at short
    # history_len it is a correctness-exercised path, not a win.
    context_parallel: bool = False
    sp_impl: str = "ring"  # "ring" | "ulysses"
    # sampled-softmax log-Q debiasing of in-batch negatives (see loss_fn);
    # uses the training set's empirical item frequency
    logq_correction: bool = True

    def __post_init__(self):
        if self.history_len > 0 and self.embed_dim % self.n_heads:
            raise ValueError(
                f"embed_dim ({self.embed_dim}) must be divisible by n_heads "
                f"({self.n_heads}) for the history encoder"
            )
        if self.sp_impl not in ("ring", "ulysses"):
            raise ValueError(f"sp_impl must be ring|ulysses, got {self.sp_impl!r}")
        if self.context_parallel and self.history_len <= 0:
            raise ValueError(
                "context_parallel requires a history encoder (history_len > 0)"
            )


class SeqEncoder(nn.Module):
    """Causal self-attention encoder over a user's recent item history.

    The consumer of ``ops.attention.fused_attention`` (pallas on TPU).
    Input: [B, T] item indices, chronological, -1 padding at the END —
    causal attention means real positions never attend to pads, and the
    masked mean-pool drops pad positions' outputs.
    """

    vocab: int
    embed_dim: int
    n_heads: int
    max_len: int
    # sequence parallelism: a mesh makes attention shard T over ``sp_axis``
    # (ring or ulysses over ICI), composed with the batch's ``dp_axis``
    # sharding. None = single-device fused_attention.
    sp_mesh: Mesh | None = None
    sp_axis: str = "model"
    dp_axis: str = "data"
    sp_impl: str = "ring"

    def _attend(self, q, k, v):  # [B, H, T, Dh] each
        from predictionio_tpu.ops.attention import (
            fused_attention,
            ring_attention,
            ulysses_attention,
        )

        mesh = self.sp_mesh
        sp_n = dict(mesh.shape).get(self.sp_axis, 1) if mesh is not None else 1
        if mesh is None or sp_n <= 1:
            return fused_attention(q, k, v, causal=True)
        T, H = q.shape[2], q.shape[1]
        # fail loud: a silent fallback here would turn the configured
        # sequence parallelism into a no-op nobody notices
        if T % sp_n:
            raise ValueError(
                f"history_len {T} not divisible by mesh axis "
                f"{self.sp_axis}={sp_n}"
            )
        batch_axis = self.dp_axis if self.dp_axis in mesh.shape else None
        if self.sp_impl == "ulysses":
            if H % sp_n:
                raise ValueError(
                    f"n_heads {H} not divisible by mesh axis "
                    f"{self.sp_axis}={sp_n} (ulysses splits heads)"
                )
            return ulysses_attention(
                q, k, v, mesh, axis=self.sp_axis, causal=True,
                batch_axis=batch_axis,
            )
        return ring_attention(
            q, k, v, mesh, axis=self.sp_axis, causal=True, batch_axis=batch_axis
        )

    @nn.compact
    def __call__(self, hist_ids: jnp.ndarray) -> jnp.ndarray:  # [B, T] -> [B, E]

        valid = hist_ids >= 0  # [B, T]
        # invalid slots (end pads AND train-time target masking, which can
        # land mid-sequence) map to a dedicated learned mask token (index
        # ``vocab``) instead of item 0 — causal followers still see the
        # key, but it carries "nothing" rather than a phantom item
        ids = jnp.where(valid, jnp.maximum(hist_ids, 0), self.vocab)
        x = nn.Embed(self.vocab + 1, self.embed_dim, name="hist_embed")(ids)
        pos = self.param(
            "pos",
            nn.initializers.normal(0.02),
            (self.max_len, self.embed_dim),
        )
        x = x + pos[None, : x.shape[1]]
        x = nn.LayerNorm(name="ln")(x)
        B, T, E = x.shape
        H = self.n_heads
        Dh = E // H

        def heads(name):
            y = nn.Dense(E, name=name)(x)
            return y.reshape(B, T, H, Dh).transpose(0, 2, 1, 3)  # [B,H,T,Dh]

        out = self._attend(heads("q"), heads("k"), heads("v"))
        out = out.transpose(0, 2, 1, 3).reshape(B, T, E)
        out = x + nn.Dense(E, name="proj")(out)  # residual
        # masked mean-pool over valid (non-pad) positions
        w = valid.astype(out.dtype)[..., None]
        denom = jnp.maximum(w.sum(axis=1), 1.0)
        return (out * w).sum(axis=1) / denom


class Tower(nn.Module):
    vocab: int
    embed_dim: int
    hidden: tuple[int, ...]
    out_dim: int

    @nn.compact
    def __call__(self, ids: jnp.ndarray, extra: jnp.ndarray | None = None) -> jnp.ndarray:
        x = nn.Embed(self.vocab, self.embed_dim, name="embed")(ids)
        if extra is not None:
            x = x + extra  # history encoding fused into the id embedding
        x = x.astype(jnp.bfloat16)
        for i, h in enumerate(self.hidden):
            x = nn.relu(nn.Dense(h, name=f"dense_{i}", dtype=jnp.bfloat16)(x))
        x = nn.Dense(self.out_dim, name="out", dtype=jnp.bfloat16)(x)
        x = x.astype(jnp.float32)
        return x / (jnp.linalg.norm(x, axis=-1, keepdims=True) + 1e-8)


class TwoTower(nn.Module):
    config: TwoTowerConfig
    # mesh for the history encoder's sequence parallelism (None = off);
    # attention carries no parameters, so checkpoints from a
    # context-parallel train load into a mesh-less serving model unchanged
    sp_mesh: Mesh | None = None

    def setup(self):
        c = self.config
        self.user_tower = Tower(c.n_users, c.embed_dim, c.hidden, c.out_dim)
        self.item_tower = Tower(c.n_items, c.embed_dim, c.hidden, c.out_dim)
        if c.history_len > 0:
            self.hist_encoder = SeqEncoder(
                c.n_items, c.embed_dim, c.n_heads, c.history_len,
                sp_mesh=self.sp_mesh if c.context_parallel else None,
                sp_impl=c.sp_impl,
            )

    def _user_extra(self, user_hist):
        if self.config.history_len > 0 and user_hist is not None:
            return self.hist_encoder(user_hist)
        return None

    def __call__(self, user_ids, item_ids, user_hist=None):
        return (
            self.user_tower(user_ids, self._user_extra(user_hist)),
            self.item_tower(item_ids),
        )

    def embed_users(self, user_ids, user_hist=None):
        return self.user_tower(user_ids, self._user_extra(user_hist))

    def embed_items(self, item_ids):
        return self.item_tower(item_ids)


def param_sharding_tree(params: Any, mesh: Mesh) -> Any:
    """Embedding tables sharded over ``model`` along vocab; rest replicated."""

    def spec_for(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if "embed" in names and getattr(leaf, "ndim", 0) == 2:
            return NamedSharding(mesh, P("model", None))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_for, params)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("data"))


def loss_fn(
    model: TwoTower,
    params,
    user_ids,
    item_ids,
    temperature: float,
    user_hist=None,
    item_log_q=None,
):
    u, v = model.apply({"params": params}, user_ids, item_ids, user_hist)
    logits = (u @ v.T) / temperature  # [B, B]
    B = u.shape[0]
    labels = jnp.arange(B)
    # sampled-softmax log-Q correction (Bengio & Senecal; the standard
    # retrieval-tower debiasing): in-batch negatives are drawn from the
    # empirical item distribution, so popular items are over-penalized as
    # negatives unless log Q(item_j) is subtracted from column j. The same
    # subtraction is a row-constant shift of logits.T, so the item->user
    # direction's softmax is untouched.
    if item_log_q is not None:
        logits = logits - item_log_q[item_ids][None, :]
    # duplicate-collision masking: when item j' == item j (same catalog item
    # drawn twice into the batch), position j' is a FALSE negative for
    # example j — its "wrong" logit is the true item's own score. Masking
    # the off-diagonal duplicates (symmetric, so it also fixes the
    # transposed direction) matters exactly when batch size is comparable
    # to the catalog, where collisions are ubiquitous.
    same_item = item_ids[None, :] == item_ids[:, None]
    dup = same_item & ~jnp.eye(B, dtype=bool)
    logits = jnp.where(dup, jnp.float32(-1e9), logits)
    # symmetric in-batch softmax (user->item and item->user)
    l1 = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
    l2 = optax.softmax_cross_entropy_with_integer_labels(logits.T, labels).mean()
    return 0.5 * (l1 + l2)


def make_train_step(
    model: TwoTower,
    tx,
    temperature: float,
    with_history: bool = False,
    item_log_q=None,
):
    if with_history:
        # history matrix [n_users, T] rides on device; per-batch rows are
        # gathered INSIDE the step (one fused gather, no host transfer)
        def train_step_h(params, opt_state, user_ids, item_ids, hist_matrix):
            h = hist_matrix[user_ids]
            # anti-leakage: the training target must not sit in its own
            # example's history (the encoder would just copy its embedding
            # and the in-batch softmax would collapse into a shortcut);
            # masked slots become the learned mask token in SeqEncoder
            h = jnp.where(h == item_ids[:, None], -1, h)
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(
                    model, p, user_ids, item_ids, temperature, h, item_log_q
                )
            )(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return train_step_h

    def train_step(params, opt_state, user_ids, item_ids):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(
                model, p, user_ids, item_ids, temperature, None, item_log_q
            )
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


@dataclasses.dataclass
class TrainResult:
    params: Any  # host-numpy pytree
    losses: list[float]
    item_embeddings: np.ndarray  # [n_items, out_dim] precomputed for serving


def build_history_matrix(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    timestamps: np.ndarray | None,
    n_users: int,
    history_len: int,
) -> np.ndarray:
    """Per-user last-``history_len`` item indices, chronological, -1 padded
    at the END (the layout SeqEncoder requires)."""
    hist = np.full((n_users, history_len), -1, np.int32)
    n = len(user_idx)
    if n == 0:
        return hist
    if timestamps is not None:
        order = np.lexsort((item_idx, timestamps, user_idx))
    else:
        # no timestamps: preserve each user's ORIGINAL event order (stable
        # sort by user only) — sorting by item id would fabricate a
        # "recency" the encoder then learns from
        order = np.argsort(user_idx, kind="stable")
    u_sorted, i_sorted = user_idx[order], item_idx[order]
    # vectorized last-K per user: each row's position within its user's
    # run -> keep only the last K rows of each run -> scatter into the K
    # slots. O(n) after the sort, no per-user python loop (the loop was
    # ~proportional to n_users; the sort dominates either way)
    starts = np.searchsorted(u_sorted, np.arange(n_users))
    deg = np.searchsorted(u_sorted, np.arange(n_users), side="right") - starts
    pos = np.arange(n) - starts[u_sorted]
    drop = np.maximum(deg - history_len, 0)[u_sorted]  # rows trimmed from front
    keep = pos >= drop
    hist[u_sorted[keep], (pos - drop)[keep]] = i_sorted[keep]
    return hist


def train_two_tower(
    user_idx: np.ndarray,
    item_idx: np.ndarray,
    config: TwoTowerConfig,
    mesh: Mesh | None = None,
    history: np.ndarray | None = None,
) -> TrainResult:
    """Full training loop: shard the interaction list, run jitted steps.

    Works on any mesh with axes (data, model) — including 1x1 (single chip)
    and the 8-device CPU test mesh. ``history`` ([n_users, history_len],
    -1-padded) enables the sequence encoder when config.history_len > 0.
    """
    if mesh is None:
        from predictionio_tpu.parallel.mesh import make_mesh

        try:
            mesh = make_mesh("data=-1,model=1")
        except ValueError:
            mesh = make_mesh("data=1,model=1")
    model = TwoTower(config, sp_mesh=mesh if config.context_parallel else None)
    rng = jax.random.PRNGKey(config.seed)
    B = min(config.batch_size, max(len(user_idx), 8))
    # round batch to a multiple of the data axis (static shapes)
    data_size = mesh.shape["data"]
    B = max(data_size, (B // data_size) * data_size)
    with_history = config.history_len > 0 and history is not None
    init_u = jnp.zeros((B,), jnp.int32)
    init_h = (
        jnp.zeros((B, config.history_len), jnp.int32) if with_history else None
    )
    params = model.init(rng, init_u, init_u, init_h)["params"]
    p_shardings = param_sharding_tree(params, mesh)
    params = jax.device_put(params, p_shardings)
    tx = optax.adam(config.learning_rate)
    opt_state = tx.init(params)
    b_sharding = batch_sharding(mesh)

    item_log_q = None
    if config.logq_correction and len(item_idx):
        freq = np.bincount(
            np.asarray(item_idx, np.int64), minlength=config.n_items
        ).astype(np.float64)
        q = freq / max(1.0, freq.sum())
        item_log_q = jax.device_put(
            jnp.asarray(np.log(np.maximum(q, 1e-12)), jnp.float32),
            NamedSharding(mesh, P()),
        )
    step = jax.jit(
        make_train_step(
            model,
            tx,
            config.temperature,
            with_history=with_history,
            item_log_q=item_log_q,
        ),
        donate_argnums=(0, 1),
    )
    hist_dev = (
        jax.device_put(
            np.asarray(history, np.int32), NamedSharding(mesh, P())
        )
        if with_history
        else None
    )

    n = len(user_idx)
    losses: list[float] = []
    start_epoch = 0
    # signature guards resume against a DIFFERENT run reusing the dir: a
    # changed config (e.g. the catalog grew, so restored embedding tables
    # would be silently too small — XLA clamps out-of-range gathers) or
    # changed training data must not resume, and a COMPLETED run's
    # checkpoint is deleted below so a scheduled retrain can never skip all
    # its epochs and return the stale parameters (code-review r4)
    run_signature = _train_signature(config, user_idx, item_idx)
    if config.checkpoint_dir and config.resume:
        state = load_train_checkpoint(config.checkpoint_dir)
        if state is not None and state.get("signature") != run_signature:
            logger.warning(
                "ignoring checkpoint in %s: it belongs to a different "
                "config/dataset", config.checkpoint_dir
            )
            state = None
        if state is not None:
            params = jax.device_put(state["params"], p_shardings)
            # optimizer moments follow their parameter's sharding
            opt_state = jax.tree_util.tree_map(
                lambda x: np.asarray(x), state["opt_state"]
            )
            opt_state = _shard_opt_state(opt_state, params, p_shardings)
            start_epoch = int(state["epoch"])
            losses = list(state["losses"])

    # One sequential rng stream for all epochs; a resumed run replays (and
    # discards) the permutations of already-completed epochs so it shuffles
    # identically to an uninterrupted run.
    shuffle_rng = np.random.default_rng(config.seed)
    steps_per_epoch = max(1, n // B)
    for epoch in range(config.epochs):
        perm = shuffle_rng.permutation(n)
        if epoch < start_epoch:
            continue
        for s in range(steps_per_epoch):
            sel = perm[s * B : (s + 1) * B]
            if len(sel) < B:  # pad by wrapping (static shapes)
                sel = np.concatenate([sel, perm[: B - len(sel)]])
            ub = jax.device_put(user_idx[sel].astype(np.int32), b_sharding)
            ib = jax.device_put(item_idx[sel].astype(np.int32), b_sharding)
            if with_history:
                params, opt_state, loss = step(params, opt_state, ub, ib, hist_dev)
            else:
                params, opt_state, loss = step(params, opt_state, ub, ib)
        losses.append(float(loss))
        if config.checkpoint_dir and (epoch + 1) % max(1, config.checkpoint_every) == 0:
            save_train_checkpoint(
                config.checkpoint_dir, params, opt_state, epoch + 1, losses,
                signature=run_signature,
            )
    if config.checkpoint_dir:
        # the checkpoint exists for crash-resume of THIS run; once complete
        # it must not survive to turn the next train into a silent no-op
        clear_train_checkpoint(config.checkpoint_dir)

    # Precompute the full item-embedding table for serving top-k.
    @jax.jit
    def embed_items(params, ids):
        return model.apply({"params": params}, ids, method=TwoTower.embed_items)

    ids = jnp.arange(config.n_items, dtype=jnp.int32)
    item_emb = np.asarray(embed_items(params, ids))
    host_params = jax.tree_util.tree_map(lambda x: np.asarray(x), params)
    return TrainResult(host_params, losses, item_emb)


def user_embedding(
    model: TwoTower, params, user_ids: jnp.ndarray, user_hist: jnp.ndarray | None = None
) -> jnp.ndarray:
    return model.apply(
        {"params": params}, user_ids, user_hist, method=TwoTower.embed_users
    )


# ---------------------------------------------------------------------------
# Mid-training checkpoint/resume
# ---------------------------------------------------------------------------

_CKPT_NAME = "twotower_train_ckpt.bin"


def _train_signature(
    config: TwoTowerConfig, user_idx: np.ndarray, item_idx: np.ndarray
) -> str:
    """Identity of one training run: the model-shaping config fields plus a
    cheap fingerprint of the interaction data. A checkpoint from a run with
    a different signature must never be resumed — restored embedding
    tables of the wrong vocab size gather out-of-bounds SILENTLY (XLA
    clamps), and a different dataset makes 'resume' meaningless."""
    import hashlib

    u = np.asarray(user_idx, np.int64)
    i = np.asarray(item_idx, np.int64)
    h = hashlib.sha1()
    for a in (u[:4096], u[-4096:], i[:4096], i[-4096:]):
        h.update(np.ascontiguousarray(a).tobytes())
    key = (
        config.n_users, config.n_items, config.embed_dim, tuple(config.hidden),
        config.out_dim, config.history_len, config.n_heads, config.seed,
        config.batch_size, len(u), h.hexdigest(),
    )
    return hashlib.sha1(repr(key).encode()).hexdigest()


def save_train_checkpoint(
    directory, params, opt_state, epoch: int, losses, signature: str = ""
) -> str:
    """Atomic epoch checkpoint: params + optimizer moments + progress,
    all pulled to host numpy so the blob is device- and sharding-agnostic
    (same contract as the model repository, ``workflow/model_io.py``)."""
    import os

    from predictionio_tpu.workflow.model_io import serialize_models

    host = jax.tree_util.tree_map(lambda x: np.asarray(x), (params, opt_state))
    blob = serialize_models(
        [
            {
                "params": host[0],
                "opt_state": host[1],
                "epoch": epoch,
                "losses": list(losses),
                "signature": signature,
            }
        ]
    )
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, _CKPT_NAME)
    tmp = path + ".tmp"
    with open(tmp, "wb") as fh:
        fh.write(blob)
    os.replace(tmp, path)
    return path


def clear_train_checkpoint(directory) -> None:
    """Remove a run's checkpoint (called when training completes)."""
    import contextlib
    import os

    with contextlib.suppress(FileNotFoundError):
        os.unlink(os.path.join(directory, _CKPT_NAME))


def load_train_checkpoint(directory) -> dict | None:
    import os

    from predictionio_tpu.workflow.model_io import deserialize_models

    path = os.path.join(directory, _CKPT_NAME)
    if not os.path.exists(path):
        return None
    with open(path, "rb") as fh:
        return deserialize_models(fh.read())[0]


def _shard_opt_state(host_opt_state, params, p_shardings):
    """Re-land restored optimizer moments with each parameter's sharding.

    Optax moment trees (mu/nu) mirror the parameter pytree *structurally*, so
    any subtree of the optimizer state whose treedef equals the parameter
    treedef gets the parameter shardings mapped leaf-for-leaf; everything else
    (scalar ``count`` etc.) is replicated. Structural matching avoids the
    suffix-collision hazard of name-based matching when one parameter path is
    a suffix of another.
    """
    param_treedef = jax.tree_util.tree_structure(params)

    def mirrors_params(node):
        try:
            return jax.tree_util.tree_structure(node) == param_treedef
        except Exception:
            return False

    def put(node):
        if mirrors_params(node):
            return jax.tree_util.tree_map(jax.device_put, node, p_shardings)
        return jax.device_put(node)

    return jax.tree_util.tree_map(put, host_opt_state, is_leaf=mirrors_params)
