"""ArchConfig -> model: init / train / prefill / decode, all pure fns.

``LM`` is a thin namespace object: it owns no arrays, only the StackCfgs
derived from an ArchConfig, and exposes pure functions that the training
and serving drivers jit under a mesh.

Frontends (assignment: "the modality frontend is a STUB —
``input_specs()`` provides precomputed frame/patch embeddings"):

  * ``token``  — ordinary token LM;
  * ``embed``  — VLM (llava): training consumes precomputed early-fusion
    patch+text embeddings (B, S, d); decode continues from the token
    embedding table (text continuation);
  * ``encdec`` — audio (seamless): encoder over precomputed frame
    embeddings (B, S_enc, d), decoder over tokens with cross-attention.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain_activation

from . import transformer as T
from .layers import apply_norm, embed, init_embedding, init_norm, unembed

__all__ = ["LM", "build_model", "softmax_xent"]


def softmax_xent(logits, labels, mask, z_coef: float = 1e-4):
    """Masked mean cross-entropy + z-loss, computed in f32.

    The gold logit is extracted with a fused masked reduction rather than
    ``take_along_axis``: under a vocab-sharded (TP) logits layout the
    gather would force an all-gather of the full (B, S, V) tensor, while
    the iota-compare-reduce stays sharded and psums a (B, S) scalar."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.where(vocab_iota == labels[..., None], logits, 0.0).sum(-1)
    xent = logz - gold
    zloss = z_coef * (logz ** 2)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = ((xent + zloss) * mask).sum() / denom
    return loss, {"xent": (xent * mask).sum() / denom}


class LM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.stack = T.make_stack_cfg(cfg, cfg.pattern, cfg.n_layers)
        if cfg.is_encdec:
            self.enc_stack = T.make_stack_cfg(cfg, ("enc",), cfg.n_enc_layers)
            self.dec_stack = T.make_stack_cfg(cfg, ("xattn",), cfg.n_layers)
        else:
            self.enc_stack = self.dec_stack = None

    # -- params ------------------------------------------------------------
    def init(self, key) -> Dict:
        cfg = self.cfg
        ks = jax.random.split(key, 6)
        p = {
            "embed": init_embedding(ks[0], cfg.padded_vocab, cfg.d_model),
            "final_norm": init_norm(cfg.d_model, kind=cfg.norm_kind),
        }
        if cfg.is_encdec:
            p["encoder"] = T.init_stack(ks[1], self.enc_stack)
            p["enc_norm"] = init_norm(cfg.d_model, kind=cfg.norm_kind)
            p["decoder"] = T.init_stack(ks[2], self.dec_stack)
        else:
            p["stack"] = T.init_stack(ks[1], self.stack)
        if not cfg.tie_embeddings:
            p["lm_head"] = init_embedding(ks[3], cfg.padded_vocab, cfg.d_model)
        return p

    def param_count(self, params) -> int:
        return sum(x.size for x in jax.tree.leaves(params))

    # -- helpers -----------------------------------------------------------
    def _embed_tokens(self, params, tokens, dtype):
        x = embed(params["embed"], tokens, dtype)
        if self.cfg.emb_scale:
            x = x * jnp.sqrt(float(self.cfg.d_model)).astype(dtype)
        return x

    def _logits(self, params, x):
        x = apply_norm(params["final_norm"], x, kind=self.cfg.norm_kind)
        table = params["lm_head" if "lm_head" in params else "embed"]
        logits = unembed(table, x)
        if self.cfg.padded_vocab != self.cfg.vocab:
            vocab_iota = jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, logits.ndim - 1
            )
            logits = jnp.where(vocab_iota < self.cfg.vocab, logits, -1e30)
        return logits

    def _encode(self, params, src_frames, remat=True):
        h, _ = T.stack_train(
            params["encoder"], src_frames, self.enc_stack, remat=remat
        )
        return apply_norm(params["enc_norm"], h, kind=self.cfg.norm_kind)

    # -- training ----------------------------------------------------------
    def train_logits(self, params, batch, *, dtype=jnp.bfloat16, remat=True):
        cfg = self.cfg
        if cfg.frontend == "embed":
            x = batch["embeds"].astype(dtype)
            x = constrain_activation(x, "btd")
            x, aux = T.stack_train(params["stack"], x, self.stack, remat=remat)
        elif cfg.is_encdec:
            memory = self._encode(params, batch["src_frames"].astype(dtype))
            x = self._embed_tokens(params, batch["tokens"], dtype)
            x = constrain_activation(x, "btd")
            x, aux = T.stack_train(
                params["decoder"], x, self.dec_stack, memory=memory, remat=remat
            )
        else:
            x = self._embed_tokens(params, batch["tokens"], dtype)
            x = constrain_activation(x, "btd")
            x, aux = T.stack_train(params["stack"], x, self.stack, remat=remat)
        x = constrain_activation(x, "btd")
        return self._logits(params, x), aux

    def loss_fn(self, params, batch, *, dtype=jnp.bfloat16, remat=True):
        logits, aux = self.train_logits(params, batch, dtype=dtype, remat=remat)
        logits = constrain_activation(logits, "btv")
        loss, metrics = softmax_xent(logits, batch["labels"], batch["loss_mask"])
        total = loss + 1e-2 * aux
        metrics["aux"] = aux
        return total, metrics

    # -- serving -----------------------------------------------------------
    def _serve_stack(self) -> T.StackCfg:
        return self.dec_stack if self.cfg.is_encdec else self.stack

    def init_caches(self, batch: int, seq_len: int, dtype=jnp.bfloat16):
        return T.init_stack_caches(self._serve_stack(), batch, seq_len, dtype)

    def insert_slot_caches(self, caches, one, slot):
        """Slot-local admission: write batch row 0 of the batch-1 cache
        pytree ``one`` (a fresh per-request prefill) into batch row
        ``slot`` of ``caches``.  No other slot's KV/state is touched."""
        return T.insert_slot_caches(caches, one, slot)

    def prefill(self, params, batch, caches, *, dtype=jnp.bfloat16):
        """Process the prompt; returns (last-position logits, caches)."""
        cfg = self.cfg
        memory = None
        if cfg.is_encdec:
            memory = self._encode(params, batch["src_frames"].astype(dtype))
            x = self._embed_tokens(params, batch["tokens"], dtype)
            x, caches = T.stack_prefill(
                params["decoder"], x, self.dec_stack, caches, memory=memory
            )
        elif cfg.frontend == "embed":
            x = batch["embeds"].astype(dtype)
            x, caches = T.stack_prefill(params["stack"], x, self.stack, caches)
        else:
            x = self._embed_tokens(params, batch["tokens"], dtype)
            x = constrain_activation(x, "btd")
            x, caches = T.stack_prefill(params["stack"], x, self.stack, caches)
        logits = self._logits(params, x[:, -1:])
        return logits, caches

    def decode(self, params, caches, tokens, pos, *, dtype=jnp.bfloat16,
               ffn=None, ffn_xs=None):
        """One token for every sequence.  tokens: (B, 1) int32; ``pos`` is
        a scalar or a (B,) int32 vector of per-sequence positions (mixed
        prompt lengths decode each row at its own position).  ``ffn`` and
        ``ffn_xs`` plug an operator into every block's second residual
        branch (``transformer.stack_decode``; GUST serving plugs its
        plans in here).  Returns (logits, caches, routes): ``routes``
        (L, B, held), the held experts each row was routed to in each
        expert layer, or None."""
        cfg = self.cfg
        x = self._embed_tokens(params, tokens, dtype)
        stack_params = params["decoder"] if cfg.is_encdec else params["stack"]
        x, caches, routes = T.stack_decode(
            stack_params, x, self._serve_stack(), caches, pos, ffn=ffn,
            ffn_xs=ffn_xs)
        logits = self._logits(params, x)
        return logits, caches, routes

    def decode_step(self, params, caches, tokens, pos, *, dtype=jnp.bfloat16):
        """:meth:`decode` with the blocks' own operators: (logits, caches)."""
        logits, caches, _ = self.decode(params, caches, tokens, pos,
                                        dtype=dtype)
        return logits, caches

    # -- input specs (ShapeDtypeStructs for the dry-run) ---------------------
    def input_specs(self, seq_len: int, batch: int, kind: str) -> Dict:
        """Stand-ins for every model input of a given shape cell (weak-type
        correct, shardable, no allocation)."""
        cfg = self.cfg
        sds = jax.ShapeDtypeStruct
        i32, f32 = jnp.int32, jnp.float32
        if kind in ("train", "prefill"):
            specs: Dict = {}
            if cfg.frontend == "embed":
                specs["embeds"] = sds((batch, seq_len, cfg.d_model), jnp.bfloat16)
            elif cfg.is_encdec:
                enc_s = min(seq_len, cfg.enc_seq or seq_len)
                specs["src_frames"] = sds((batch, enc_s, cfg.d_model), jnp.bfloat16)
                specs["tokens"] = sds((batch, seq_len), i32)
            else:
                specs["tokens"] = sds((batch, seq_len), i32)
            if kind == "train":
                specs["labels"] = sds((batch, seq_len), i32)
                specs["loss_mask"] = sds((batch, seq_len), f32)
            return specs
        if kind == "decode":
            return {"tokens": sds((batch, 1), i32)}
        raise ValueError(kind)


def build_model(cfg: ArchConfig) -> LM:
    return LM(cfg)
