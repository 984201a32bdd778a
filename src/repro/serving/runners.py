"""ModelRunner: the seam between ``ServingEngine`` and ``repro.models``.

The engine used to hardcode decoder-only semantics — token-in/logits-out
step, KV-strip/paged state reset-attach-copy via path-name matching, and
``fits()`` measured in KV tokens.  A ``ModelRunner`` owns everything the
engine needs to know about one architecture family:

  * ``init_state``      — allocate the batched decode state
  * ``make_step`` / ``make_prefill`` — build the pure functions the engine
    jits (decode tick / bucketed chunk pass), closure-identical to the
    pre-runner engine so greedy decode stays bit-identical
  * ``make_reset`` / ``make_attach`` / ``make_copy_page`` — the compile-once
    slot-state scatter passes (admission reset, prefix-cache attach, CoW
    page duplication)
  * ``make_admit``      — optional per-slot admission pass (EncDec: one
    encoder forward cached as cross-attention KV)
  * ``state_spec`` / ``shard_state`` — mesh placement of the decode state
  * ``capacity_cost``   — pages a request of N total tokens will occupy
    (attention KV) or 0 (recurrent state is O(1) per slot)

Three implementations cover the zoo (see ``runner_for``):

  * ``DecoderRunner``   — decoder-only full-attention LMs (KV caches grow
    per token; paged pool eligible).
  * ``RecurrentRunner`` — ssm / hybrid archs (xlstm, recurrentgemma):
    decode state is FIXED-SIZE (recurrent folds + ring-buffer window
    caches), so requests bypass page accounting entirely and are never
    preempted by pool pressure.
  * ``EncDecRunner``    — whisper-style encoder-decoder: one encoder pass
    at admission, cached per slot as cross-attention K/V in the decode
    state; decode then proceeds like a decoder-only model (the
    self-attention KV still pages normally).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import (
    decode_step,
    encode,
    encode_cross_kv,
    fused_decode_layers,
    init_decode_state,
    prefill,
    sample_tokens,
)
from repro.models.layers import Numerics
from repro.serving.pages import pages_needed


def _names(path):
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


def _batch_axis(names) -> int:
    """Leaves stacked over scan groups — and the EncDec per-slot encoder
    cache, which carries a leading (n_groups,) axis too — hold the slot
    batch at axis 1; everything else at axis 0."""
    return 1 if ("groups" in names or "enc" in names) else 0


class ModelRunner:
    """Decoder-only behavior; the base class IS ``DecoderRunner``'s
    implementation and the other runners override only what differs."""

    #: May this model's KV state live in the shared page pool?
    paged_ok: bool = False
    #: O(1) decode state per slot (bypasses max_len and page accounting)?
    fixed_state: bool = False
    #: Does admission need a jitted per-slot pass (``make_admit``)?
    needs_admission: bool = False
    #: Is cross-request prefix-page sharing sound for this model?  (False
    #: when decoder state depends on per-request side inputs — EncDec.)
    prefix_cache_ok: bool = True
    #: Layers whose decode tick runs the fused attention kernel, counted
    #: when the step was last traced (``models.fused_decode_layers``).
    fused_layers: int = 0

    def __init__(self, mcfg: ModelConfig):
        self.mcfg = mcfg

    # -- state ------------------------------------------------------------
    def init_state(self, capacity: int, max_len: int, *,
                   page_size: Optional[int] = None,
                   pool_pages: Optional[int] = None) -> dict:
        return init_decode_state(self.mcfg, capacity, max_len,
                                 page_size=page_size, pool_pages=pool_pages)

    def state_spec(self, state, mesh):
        from repro.distributed.sharding import serving_state_spec_tree
        return serving_state_spec_tree(state, mesh)

    def shard_state(self, state, mesh):
        from repro.distributed.sharding import shard_decode_state
        return shard_decode_state(state, mesh)

    # -- capacity ---------------------------------------------------------
    def capacity_cost(self, total_tokens: int, page_size: int) -> int:
        """Pages a request of ``total_tokens`` (prompt + max_new) occupies
        at full length.  Attention KV grows per token; recurrent state
        overrides this to 0."""
        return pages_needed(total_tokens, page_size)

    def accepts(self, req) -> bool:
        """Model-specific request validation beyond the engine's generic
        ``fits()`` (prompt shape, side inputs...)."""
        return True

    # -- jit-ready closures (the engine jits these verbatim) ---------------
    @staticmethod
    def _replicated(x, mesh):
        """Pin a sampled-token array to a canonical replicated sharding so
        the warmed executables accept it back as the next pass's input
        (the engine feeds device samples straight into the next dispatch
        without ever fetching them)."""
        if mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec()))

    def _step_core(self, params, state, token, key, quant, mesh):
        """The model-family decode-tick body shared by BOTH closure forms
        below (legacy logits-out and sampled); overriding this is how a
        family changes its step without touching sampling."""
        nx = Numerics(quant, key, mesh=mesh)
        return decode_step(params, state, token, self.mcfg, nx)

    def _prefill_core(self, params, state, tokens, n_tokens, key, quant,
                      mesh):
        nx = Numerics(quant, key, mesh=mesh)
        return prefill(params, state, tokens, n_tokens, self.mcfg, nx)

    def _traced_step(self, params, state, token, key, quant, mesh):
        """``_step_core``, counting ``fused_layers`` as it is traced."""
        self.fused_layers = fused_decode_layers(
            params, state, self.mcfg, Numerics(quant, mesh=mesh))
        return self._step_core(params, state, token, key, quant, mesh)

    def make_step(self, quant, mesh, seed=None):
        """Build the jit-ready decode-tick closure.

        ``quant`` selects the whole numerics stack inside the closure via
        ``Numerics``: with ``mode="abfp_fused"`` (and the weights packed
        with per-tile gains at engine init) every decode tick's attention
        block routes through the fused QKV + quantized-attention kernels
        of ``kernels.abfp_decode_fused``; the closure itself is identical
        across modes, so the engine jits exactly one step function either
        way.

        With ``seed=None`` (the legacy form external callers use) the
        closure is ``(params, state, token, key) -> (logits, new_state)``.
        With an integer seed the engine gets the SAMPLED form the serving
        tick runs: ``(params, state, token, ov_vals, ov_mask, key, temps,
        uids, idxs) -> (logits, sampled, new_state)`` — the next token is
        drawn on device (``models.sample_tokens``) so the overlapped
        runtime never syncs logits to the host, and ``ov_mask`` lets the
        host override per-slot inputs (prompt feeds) while every other
        slot consumes the previous pass's device sample.  Both forms wrap
        the same ``_step_core`` body, so the logits math is identical.
        """
        if seed is None:
            def _step(params, state, token, key):
                return self._traced_step(params, state, token, key, quant,
                                         mesh)

            return _step

        def _step(params, state, token, ov_vals, ov_mask, key, temps, uids,
                  idxs):
            tok = jnp.where(ov_mask, ov_vals, token)
            logits, new_state = self._traced_step(params, state, tok, key,
                                                  quant, mesh)
            nxt = self._replicated(
                sample_tokens(logits, temps, uids, idxs, seed), mesh)
            return logits, nxt, new_state

        return _step

    def make_prefill(self, quant, mesh, seed=None):
        """Legacy form (``seed=None``): ``(params, state, tokens, n_tokens,
        key) -> (logits, new_state)``.  Sampled form: adds ``riders`` /
        ``rider_mask`` — decode slots riding along in a chunk pass take
        their single input token from the previous pass's on-device sample
        instead of a host value — and returns ``(logits, sampled,
        new_state)`` like the sampled step."""
        if seed is None:
            def _prefill(params, state, tokens, n_tokens, key):
                return self._prefill_core(params, state, tokens, n_tokens,
                                          key, quant, mesh)

            return _prefill

        def _prefill(params, state, tokens, n_tokens, riders, rider_mask,
                     key, temps, uids, idxs):
            first = jnp.where(rider_mask, riders, tokens[:, 0])
            toks = tokens.at[:, 0].set(first)
            logits, new_state = self._prefill_core(
                params, state, toks, n_tokens, key, quant, mesh)
            nxt = self._replicated(
                sample_tokens(logits, temps, uids, idxs, seed), mesh)
            return logits, nxt, new_state

        return _prefill

    def make_admit(self, quant, mesh):
        raise NotImplementedError(
            f"{type(self).__name__} has no admission pass")

    def make_reset(self):
        def _reset(state, i):
            def reset(path, leaf):
                names = _names(path)
                if names[-1].endswith("_pages") or names[-1] == "page_table":
                    # Pool pages are GLOBAL (other slots own them); the
                    # page table is host-owned and refreshed every pass.
                    return leaf
                b_axis = _batch_axis(names)
                if leaf.ndim <= b_axis:
                    return leaf
                idx = (slice(None),) * b_axis + (i,)
                fill = (-1e30 if names[-1] == "m" and leaf.ndim - b_axis == 3
                        else 0)
                return leaf.at[idx].set(jnp.asarray(fill, leaf.dtype))

            return jax.tree_util.tree_map_with_path(reset, state)

        return _reset

    def make_attach(self):
        def _attach(state, i, length):
            # Prefix-cache attach: slot i starts mid-sequence — its cache
            # length and rope position jump to the shared-prefix length.
            def setl(path, leaf):
                names = _names(path)
                if names[-1] not in ("position", "length"):
                    return leaf
                b_axis = _batch_axis(names)
                idx = (slice(None),) * b_axis + (i,)
                return leaf.at[idx].set(jnp.asarray(length, leaf.dtype))

            return jax.tree_util.tree_map_with_path(setl, state)

        return _attach

    def make_copy_page(self):
        def _copy_page(state, src, dst):
            # Copy-on-write: duplicate one physical page across every
            # layer's pool (src/dst are data, so one compile serves all
            # CoW splits).
            def cp(path, leaf):
                names = _names(path)
                if not names[-1].endswith("_pages"):
                    return leaf
                if "groups" in names:
                    return leaf.at[:, dst].set(leaf[:, src])
                return leaf.at[dst].set(leaf[src])

            return jax.tree_util.tree_map_with_path(cp, state)

        return _copy_page


class DecoderRunner(ModelRunner):
    """Decoder-only (and any full-attention) LM: KV caches grow per token
    and may live in the shared page pool."""

    fixed_state = False
    needs_admission = False

    @property
    def paged_ok(self) -> bool:
        return self.mcfg.attention_type == "full"


class RecurrentRunner(ModelRunner):
    """ssm / hybrid archs (xlstm, recurrentgemma): recurrent folds and
    ring-buffer window caches are FIXED-SIZE per slot, so requests bypass
    page accounting (``capacity_cost == 0``), are admissible at any total
    length, and can never be preempted by pool pressure (their lane runs
    unpaged — ``paged_ok`` is False)."""

    paged_ok = False
    fixed_state = True

    def capacity_cost(self, total_tokens: int, page_size: int) -> int:
        return 0


class EncDecRunner(ModelRunner):
    """Whisper-style encoder-decoder.  Admission runs ONE jitted encoder
    pass over the request's frontend features and scatters the resulting
    cross-attention K/V into the slot's ``state["enc"]`` cache; decode then
    proceeds exactly like a decoder-only model, with the cached enc K/V
    threaded into every pass.  The decoder's own self-attention KV still
    pages normally (whisper is full-attention), but prefix-page sharing is
    DISABLED: decoder KV depends on the per-request encoder output, so two
    requests with equal prompts but different audio must not share pages.

    ``enc_len`` is the fixed encoder frame count (one jit compile); a
    request must carry ``features`` of shape (enc_len, d_model)."""

    needs_admission = True
    prefix_cache_ok = False

    DEFAULT_ENC_LEN = 64

    def __init__(self, mcfg: ModelConfig, enc_len: int = DEFAULT_ENC_LEN):
        assert mcfg.is_encoder_decoder, mcfg.name
        super().__init__(mcfg)
        self.enc_len = int(enc_len)

    @property
    def paged_ok(self) -> bool:
        return self.mcfg.attention_type == "full"

    def accepts(self, req) -> bool:
        feats = getattr(req, "features", None)
        if feats is None:
            return False
        shape = tuple(getattr(feats, "shape", ()))
        return shape == (self.enc_len, self.mcfg.d_model)

    def init_state(self, capacity: int, max_len: int, *,
                   page_size: Optional[int] = None,
                   pool_pages: Optional[int] = None) -> dict:
        state = super().init_state(capacity, max_len, page_size=page_size,
                                   pool_pages=pool_pages)
        mcfg = self.mcfg
        pattern = mcfg.block_pattern or ("attention",)
        n_groups = mcfg.num_layers // len(pattern)
        kh, hd = mcfg.num_kv_heads, mcfg.resolved_head_dim
        # Per-slot encoder K/V, one entry per pattern position, stacked
        # over scan groups like params["groups"] — consumed by decode_step
        # / prefill via their ``enc_kv`` scan input.
        state["enc"] = tuple(
            {"k": jnp.zeros((n_groups, capacity, self.enc_len, kh, hd),
                            mcfg.activation_dtype),
             "v": jnp.zeros((n_groups, capacity, self.enc_len, kh, hd),
                            mcfg.activation_dtype)}
            for _ in pattern)
        return state

    @staticmethod
    def _split_enc(state):
        enc = state["enc"]
        rest = {k: v for k, v in state.items() if k != "enc"}
        enc_kv = [(e["k"], e["v"]) for e in enc]
        return rest, enc, enc_kv

    def _step_core(self, params, state, token, key, quant, mesh):
        rest, enc, enc_kv = self._split_enc(state)
        nx = Numerics(quant, key, mesh=mesh)
        logits, new_state = decode_step(params, rest, token, self.mcfg, nx,
                                        enc_kv=enc_kv)
        new_state["enc"] = enc
        return logits, new_state

    def _prefill_core(self, params, state, tokens, n_tokens, key, quant,
                      mesh):
        rest, enc, enc_kv = self._split_enc(state)
        nx = Numerics(quant, key, mesh=mesh)
        logits, new_state = prefill(params, rest, tokens, n_tokens,
                                    self.mcfg, nx, enc_kv=enc_kv)
        new_state["enc"] = enc
        return logits, new_state

    def make_admit(self, quant, mesh):
        """One encoder pass for slot ``i``: features (enc_len, d_model) ->
        cross-attention K/V scattered into ``state["enc"]`` at batch row i.
        Slot index and features are data — one compile serves every
        admission."""
        mcfg = self.mcfg

        def _admit(params, state, features, i, key):
            nx = Numerics(quant, key, mesh=mesh)
            enc_out = encode(params, features[None], mcfg, nx)   # (1, S, d)
            kv = encode_cross_kv(params, enc_out, mcfg, nx)
            new_enc = []
            for j, (k, v) in enumerate(kv):
                e = state["enc"][j]
                new_enc.append({
                    "k": e["k"].at[:, i].set(k[:, 0].astype(e["k"].dtype)),
                    "v": e["v"].at[:, i].set(v[:, 0].astype(e["v"].dtype)),
                })
            out = dict(state)
            out["enc"] = tuple(new_enc)
            return out

        return _admit


def runner_for(mcfg: ModelConfig, **kwargs) -> ModelRunner:
    """Default runner for a config: EncDec for encoder-decoder models,
    Recurrent when the block pattern carries any non-attention kind
    (``attention_type`` hybrid/recurrent — fixed-size decode state), else
    plain Decoder."""
    if mcfg.is_encoder_decoder:
        return EncDecRunner(mcfg, **kwargs)
    if mcfg.attention_type in ("hybrid", "recurrent"):
        return RecurrentRunner(mcfg, **kwargs)
    return DecoderRunner(mcfg, **kwargs)
