"""Sharding rules: parameter-path → PartitionSpec, ZeRO-1 state sharding.

Megatron-style tensor parallelism over the 'model' axis + data parallelism
over ('pod', 'data'):

  wq/wk/wv        (d, heads*hd)  -> shard output (heads) over 'model'
  wo              (heads*hd, d)  -> shard input  (heads) over 'model'
  mlp wi/wg       (d, ff)        -> shard ff over 'model'
  mlp wo          (ff, d)        -> shard ff over 'model'
  moe wi/wg/wo    (E, d, ff)     -> shard experts over 'model' (EP)
  embed           (V, d)         -> shard vocab over 'model'
  lm_head         (d, V)         -> shard vocab over 'model'
  recurrent/xlstm projections    -> shard the wide axis over 'model'
  norms / scalars                -> replicated

Stacked-layer leaves carry a leading (n_groups,) scan axis: specs are
shifted right by one.  Activations: batch over ('pod', 'data').

ZeRO-1: optimizer moments and f32 masters additionally shard their largest
replicated axis over 'data' when divisible — cutting optimizer memory by the
DP degree, the standard trick for fitting large models.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.abfp import PackedWeight

Pytree = Any

MODEL_AXIS = "model"
DATA_AXES = ("pod", "data")      # 'pod' present only on the multi-pod mesh
_LANE = 128                      # PackedWeight column alignment (core.abfp)


def _data_axes(mesh: Mesh):
    return tuple(a for a in DATA_AXES if a in mesh.axis_names)


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        return int(np.prod([mesh.shape[a] for a in entry]))
    return mesh.shape[entry]


def validate_spec(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Drop sharding on any dim not divisible by its axis-group size.

    Production meshes meet most configs exactly; the exceptions (vocab 51865
    whisper / 49155 granite, global_batch=1 long-context cells) degrade to
    replication on that dim instead of failing to lower.
    """
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, entry in zip(shape, parts[: len(shape)]):
        if entry is not None and dim % _axis_size(mesh, entry) != 0:
            entry = None
        out.append(entry)
    return P(*out)


def batch_spec(mesh: Mesh, shape: tuple) -> P:
    """Activations / token batches: batch dim over (pod, data), validated."""
    spec = P(_data_axes(mesh), *([None] * (len(shape) - 1)))
    return validate_spec(spec, tuple(shape), mesh)


# Rules matched against the *last* path components (innermost name wins).
# value = spec for the UNSTACKED 2-D/3-D weight.
_RULES = [
    # attention projections
    (("attn", "wq"), P(None, MODEL_AXIS)),
    (("attn", "wk"), P(None, MODEL_AXIS)),
    (("attn", "wv"), P(None, MODEL_AXIS)),
    (("attn", "wo"), P(MODEL_AXIS, None)),
    (("cross", "wq"), P(None, MODEL_AXIS)),
    (("cross", "wk"), P(None, MODEL_AXIS)),
    (("cross", "wv"), P(None, MODEL_AXIS)),
    (("cross", "wo"), P(MODEL_AXIS, None)),
    # dense MLP
    (("mlp", "wi"), P(None, MODEL_AXIS)),
    (("mlp", "wg"), P(None, MODEL_AXIS)),
    (("mlp", "wo"), P(MODEL_AXIS, None)),
    # MoE: expert parallelism
    (("moe", "router"), P(None, None)),
    (("moe", "wi"), P(MODEL_AXIS, None, None)),
    (("moe", "wg"), P(MODEL_AXIS, None, None)),
    (("moe", "wo"), P(MODEL_AXIS, None, None)),
    # Griffin recurrent block
    (("rglru", "w_in"), P(None, MODEL_AXIS)),
    (("rglru", "w_gate"), P(None, MODEL_AXIS)),
    (("rglru", "w_rg"), P(None, MODEL_AXIS)),
    (("rglru", "w_ig"), P(None, MODEL_AXIS)),
    (("rglru", "w_out"), P(MODEL_AXIS, None)),
    (("rglru", "conv_w"), P(None, MODEL_AXIS)),
    (("rglru", "lam"), P(MODEL_AXIS)),
    # xLSTM
    (("mlstm", "w_up"), P(None, MODEL_AXIS)),
    (("mlstm", "w_gate"), P(None, MODEL_AXIS)),
    (("mlstm", "wq"), P(None, MODEL_AXIS)),
    (("mlstm", "wk"), P(None, MODEL_AXIS)),
    (("mlstm", "wv"), P(None, MODEL_AXIS)),
    (("mlstm", "w_if"), P(None, None)),
    (("mlstm", "w_down"), P(MODEL_AXIS, None)),
    (("mlstm", "skip_scale"), P(MODEL_AXIS)),
    (("slstm", "w_x"), P(None, MODEL_AXIS)),
    (("slstm", "r_h"), P(None, None, None)),   # block-diagonal, small
    (("slstm", "b"), P(None)),
    (("slstm", "w_up"), P(None, MODEL_AXIS)),
    (("slstm", "w_down"), P(MODEL_AXIS, None)),
    # embeddings / head
    (("embed",), P(MODEL_AXIS, None)),
    (("lm_head",), P(None, MODEL_AXIS)),
]


def _path_names(path) -> tuple:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(str(k.idx))
        else:
            names.append(str(k))
    return tuple(names)


def _match(names: tuple) -> Optional[P]:
    for pattern, spec in _RULES:
        m = len(pattern)
        # match pattern against the tail, ignoring numeric path components
        filt = tuple(n for n in names if not n.isdigit())
        if filt[-m:] == pattern:
            return spec
    return None


def _is_stacked(names: tuple) -> bool:
    """Leaves under groups/<j>/... or encoder/layers/... have a leading scan
    axis."""
    return ("groups" in names) or ("layers" in names)


def _leaf_base_spec(names: tuple, ndim: int) -> P:
    """Rule-matched, rank-adjusted spec for one leaf (unvalidated)."""
    spec = _match(names)
    if spec is None:
        return P(*([None] * ndim))                  # norms, biases, scalars
    if _is_stacked(names):
        spec = P(None, *spec)                       # leading scan axis
    if len(spec) != ndim:
        # rank mismatch (e.g. lam under stacking) — pad/trim safely
        parts = tuple(spec) + (None,) * max(0, ndim - len(spec))
        spec = P(*parts[:ndim])
    return spec


def _leaf_demote_k(names: tuple, ndim: int, spec: P) -> P:
    """Drop MODEL sharding from a weight's contraction (K) axis — ABFP
    tiles of width n must not straddle shards and the tile scan axis must
    not be sharded (see ``abfp_param_spec_tree``)."""
    parts = list(spec)
    if not parts:
        return spec
    # Stacked leaves: axis 0 is the scan axis; K is the first non-stack
    # axis for 2-D weights (rank>=2 after stacking).
    k_axis = 1 if _is_stacked(names) else 0
    if ndim >= 2 and len(parts) > k_axis and parts[k_axis] == MODEL_AXIS:
        parts[k_axis] = None
    # MoE expert axis (axis 0/1) is not a contraction — keep EP sharding.
    return P(*parts)


def param_spec_tree(params: Pytree, mesh: Optional[Mesh] = None) -> Pytree:
    """PartitionSpec pytree mirroring ``params`` (validated when mesh given)."""

    def one(path, leaf):
        spec = _leaf_base_spec(_path_names(path), leaf.ndim)
        if mesh is not None:
            spec = validate_spec(spec, leaf.shape, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(one, params)


def shard_params(params: Pytree, mesh: Mesh) -> Pytree:
    specs = param_spec_tree(params)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs)


def named_sharding_tree(params: Pytree, mesh: Mesh) -> Pytree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_spec_tree(params))


def abfp_param_spec_tree(params: Pytree, mesh: Optional[Mesh] = None) -> Pytree:
    """Param specs for ABFP-simulation (QAT / ABFP-serve) cells.

    The ABFP tile scan requires the contraction (K) axis of every quantized
    matmul to be shard-local (tiles of width n must not straddle shards and
    the scan axis must not be sharded).  Column-parallel sharding (output
    features over 'model') is always safe; row-parallel specs (K over
    'model') are demoted to replicated.  See EXPERIMENTS.md §Dry-run.
    """
    def one(path, leaf):
        names = _path_names(path)
        spec = _leaf_demote_k(names, leaf.ndim,
                              _leaf_base_spec(names, leaf.ndim))
        if mesh is not None:
            spec = validate_spec(spec, leaf.shape, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# Serving placement: packed/float param trees + decode state, mesh-aware
# ---------------------------------------------------------------------------


def serving_param_spec_tree(params: Pytree, mesh: Mesh,
                            quant: Any = None) -> Pytree:
    """Column-parallel-only specs for a serving param tree (float or packed).

    Float leaves follow the ABFP rules (output features over 'model',
    K-sharding demoted): exactly the axes ``kernels.ops.dense_tp`` splits.
    ``PackedWeight`` leaves shard their int8 codes AND bf16 scales together
    along the output-column axis — the per-(tile, col) scales always travel
    with their codes.  Shard-or-replicate is decided by the SAME predicate
    the dispatch uses (``kernels.ops.tp_col_quantum``, given ``quant``), so
    a weight is stored sharded exactly when the matmul consumes it sharded
    — no per-call resharding either way.  Without ``quant`` the
    conservative noise-safe quantum (whole 128-lane blocks per shard)
    applies to kernel-mode weights.
    """
    from repro.kernels.ops import tp_col_quantum

    tp = mesh.shape[MODEL_AXIS] if MODEL_AXIS in mesh.axis_names else 1

    def col_quantum(packed: bool) -> Optional[int]:
        if quant is not None:
            return tp_col_quantum(quant, packed, tp)
        return tp * _LANE if packed else tp     # noise-safe default

    def one(path, leaf):
        if isinstance(leaf, PackedWeight):
            lead = leaf.ndim - 2
            q = col_quantum(True)
            col = (MODEL_AXIS
                   if tp > 1 and q is not None and leaf.n_padded % q == 0
                   else None)
            cs = P(*((None,) * (lead + 1)), col)
            # A PackedWeight of specs: flattens to (codes_spec, scales_spec
            # [, gains_spec]) with the SAME aux as the param leaf, so
            # jax.device_put can zip the two trees leaf-for-leaf.  Per-tile
            # gains index the (contracting) K axis — every column shard
            # needs the full vector — so they replicate.
            gs = (None if leaf.gains is None
                  else P(*((None,) * leaf.gains.ndim)))
            return PackedWeight(cs, cs, leaf.k, leaf.n_cols,
                                leaf.tile_width, leaf.bits_w, gains=gs)
        names = _path_names(path)
        spec = _leaf_demote_k(names, leaf.ndim,
                              _leaf_base_spec(names, leaf.ndim))
        spec = validate_spec(spec, leaf.shape, mesh)
        parts = tuple(spec) + (None,) * (leaf.ndim - len(spec))
        if parts and parts[-1] == MODEL_AXIS:
            q = col_quantum(False)
            if q is None or leaf.shape[-1] % q != 0:
                spec = P(*parts[:-1], None)
        return spec

    return jax.tree_util.tree_map_with_path(
        one, params, is_leaf=lambda x: isinstance(x, PackedWeight))


def shard_serving_params(params: Pytree, mesh: Mesh,
                         quant: Any = None) -> Pytree:
    """Place a serving param tree (float and/or packed leaves) on ``mesh``."""
    specs = serving_param_spec_tree(params, mesh, quant)
    return jax.device_put(
        params,
        jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                     is_leaf=lambda x: isinstance(x, P)))


def serving_state_spec_tree(state: Pytree, mesh: Mesh) -> Pytree:
    """Decode-state specs for SERVING: slot/batch axis over the data axes,
    everything else replicated.

    Unlike ``decode_state_spec_tree`` (training-eval oriented), no state
    axis is put on 'model': serving activations are replicated across the
    model axis between column-parallel matmuls (``kernels.ops.dense_tp``
    all-gathers), and model-sharding KV heads would make attention
    contractions cross shards — trading the bit-identical-at-any-mesh-shape
    property for memory serving does not need at these capacities."""
    dp = _data_axes(mesh)

    def one(path, leaf):
        names = _path_names(path)
        if names[-1].endswith("_pages"):
            # Paged KV pools are GLOBAL (leading axis is the page pool, not
            # the slot batch): fully replicated so any data shard can gather
            # any page through its table rows.
            return P(*([None] * leaf.ndim))
        # "enc" leaves (cross-attention KV cached at admission) carry the
        # same leading (n_groups,) scan axis as grouped decode state.
        stacked = ("groups" in names) or ("enc" in names)
        nd = leaf.ndim - (1 if stacked else 0)
        if nd <= 0:
            return P(*([None] * leaf.ndim))
        core = (dp,) + (None,) * (nd - 1)
        if stacked:
            core = (None,) + core
        return validate_spec(P(*core), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(one, state)


def shard_decode_state(state: Pytree, mesh: Mesh) -> Pytree:
    """Place an ``init_decode_state`` tree on ``mesh`` for serving."""
    specs = serving_state_spec_tree(state, mesh)
    return jax.device_put(
        state, jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P)))


# ---------------------------------------------------------------------------
# Decode-state sharding
# ---------------------------------------------------------------------------


def decode_state_spec_tree(state: Pytree, mesh: Mesh) -> Pytree:
    """PartitionSpec tree for a ``models.init_decode_state`` pytree.

    Batch over (pod, data); the widest per-token axis over 'model' when
    divisible (KV heads, else head_dim; recurrent state width; mLSTM head
    dim).  Leaves under "groups" carry a leading stacked axis.
    """
    dp = _data_axes(mesh)
    mp = mesh.shape[MODEL_AXIS]

    def one(path, leaf):
        names = _path_names(path)
        stacked = "groups" in names
        name = names[-1]
        nd = leaf.ndim - (1 if stacked else 0)
        shape = leaf.shape[1:] if stacked else leaf.shape

        if name in ("length", "position"):
            core = (dp,)
        elif name == "kv_scale" or (name in ("k", "v")
                                    and leaf.dtype == np.int8):
            # int8 cache (B, KH, HD, S) and its scales (B, KH, 2, S)
            core = (dp, MODEL_AXIS if shape[1] % mp == 0 else None,
                    None, None)
        elif name in ("k", "v"):                   # (B, S, KH, HD)
            if shape[2] % mp == 0:
                core = (dp, None, MODEL_AXIS, None)
            elif shape[3] % mp == 0:
                core = (dp, None, None, MODEL_AXIS)
            else:
                core = (dp, None, None, None)
        elif name == "conv":                       # (B, W-1, R)
            core = (dp, None, MODEL_AXIS if shape[2] % mp == 0 else None)
        elif name == "C":                          # (B, NH, dh, dh)
            core = (dp, None, MODEL_AXIS if shape[2] % mp == 0 else None, None)
        elif nd == 3:                              # h/c/n/m (B, NH, dh)
            core = (dp, None, MODEL_AXIS if shape[2] % mp == 0 else None)
        elif nd == 2:                              # h (B, R) / m (B, NH)
            core = (dp, MODEL_AXIS if shape[1] % mp == 0 else None)
        else:
            core = (dp,) + (None,) * (nd - 1)
        if stacked:
            core = (None,) + tuple(core)
        return validate_spec(P(*core), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(one, state)


# ---------------------------------------------------------------------------
# ZeRO-1: shard optimizer state over the data axis too
# ---------------------------------------------------------------------------


def zero1_spec(spec: P, shape: tuple, mesh: Mesh) -> P:
    """Extend a param spec with 'data' sharding on the largest replicated,
    divisible axis (optimizer moments / master weights only)."""
    if "data" not in mesh.axis_names:
        return spec
    dp = mesh.shape["data"]
    parts = list(spec) + [None] * (len(shape) - len(spec))
    # pick the largest axis currently unsharded and divisible by dp
    best, best_size = None, 0
    for i, (p, s) in enumerate(zip(parts, shape)):
        if p is None and s % dp == 0 and s > best_size:
            best, best_size = i, s
    if best is None:
        return spec
    parts[best] = "data"
    return P(*parts)


def zero1_state_sharding(params: Pytree, mesh: Mesh) -> Pytree:
    """NamedSharding tree for f32 moments/masters mirroring ``params``."""
    specs = param_spec_tree(params)

    def one(p, s):
        return NamedSharding(mesh, zero1_spec(s, p.shape, mesh))

    return jax.tree.map(one, params, specs)
