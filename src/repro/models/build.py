"""Top-level Model API: init / train_step / prefill / decode_step /
input_specs — the single entry point used by the launcher, the dry-run and
the smoke tests.

Batch formats (input_specs returns matching ShapeDtypeStructs):
  text archs   {'tokens': (B,S) i32, 'targets': (B,S) i32}
  vlm          + 'vision_embeds': (B,P,D)   (stub frontend, DESIGN.md)
  audio encdec {'frames': (B,S_enc,D), 'tokens': (B,S_dec), 'targets': ...}

Decode runs ONE token against a cache of ``max_len`` (the assigned decode
shapes); ``rolling=True`` selects the sliding-window rolling cache used by
``long_500k`` on attention archs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.configs.shapes import InputShape
from repro.models import layers, transformer
from repro.models.layers import Params
from repro.optim import optimizers

Array = jax.Array

# vision prefix length comes from cfg.frontend.num_embeddings (stub ViT)
AUDIO_MEMORY = 1536        # encoder frames held as decode memory
DEC_FRACTION = 8           # enc-dec training: dec_len = seq_len // 8


@dataclasses.dataclass
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------------ init

    def init(self, key) -> Params:
        cfg = self.cfg
        k_emb, k_stack, k_norm, k_mtp, k_enc_emb = jax.random.split(key, 5)
        params: Params = {
            "embedding": layers.init_embedding(cfg, k_emb),
            "stack": transformer.init_stack(cfg, k_stack),
            "final_norm": layers.init_norm(cfg, cfg.d_model),
        }
        if cfg.mtp_depth:
            params["mtp"] = {
                "proj": layers.dense_init(
                    k_mtp, (2 * cfg.d_model, cfg.d_model),
                    layers.dtype_of(cfg)),
                "layer": transformer.init_layer(cfg, "attn_mlp", k_mtp),
                "norm": layers.init_norm(cfg, cfg.d_model),
            }
        if cfg.is_encoder_decoder:
            params["enc_final_norm"] = layers.init_norm(cfg, cfg.d_model)
        return params

    def init_optimizer(self):
        return optimizers.make(self.cfg.optimizer, self.cfg.learning_rate)

    # --------------------------------------------------------------- forward

    def _embed_inputs(self, params: Params, batch: dict) -> Array:
        x = layers.embed(params["embedding"], batch["tokens"])
        if self.cfg.arch_type == "vlm":
            x = jnp.concatenate(
                [batch["vision_embeds"].astype(x.dtype), x], axis=1)
        return x

    def forward(self, params: Params, batch: dict, *,
                window: Optional[int] = None,
                use_kernel: bool = False,
                last_only: bool = False) -> tuple[Array, Array, Array]:
        """Full forward. Returns (logits, aux_loss, hidden).

        ``last_only`` restricts the unembed to the final position (prefill:
        avoids materializing the (B, S, V) logits buffer)."""
        cfg = self.cfg
        window = window if window is not None else cfg.sliding_window
        memory = None
        if cfg.is_encoder_decoder:
            memory = self.encode(params, batch["frames"],
                                 use_kernel=use_kernel)
        x = self._embed_inputs(params, batch)
        only = ("dec",) if cfg.is_encoder_decoder else None
        x, aux = transformer.apply_stack(cfg, params["stack"], x,
                                         window=window, memory=memory,
                                         use_kernel=use_kernel,
                                         only_kinds=only)
        h = layers.apply_norm(cfg, params["final_norm"], x)
        if cfg.arch_type == "vlm":
            h = h[:, self.cfg.frontend.num_embeddings:]
        logits = layers.unembed(cfg, params["embedding"],
                                h[:, -1:] if last_only else h)
        return logits, aux, h

    def encode(self, params: Params, frames: Array,
               use_kernel: bool = False) -> Array:
        """Encoder over stubbed frame embeddings (enc-dec archs)."""
        cfg = self.cfg

        # only the 'enc' segment runs here
        def body(carry, layer_p):
            h, _ = transformer.apply_layer(cfg, "enc", layer_p, carry)
            return h, None
        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, frames, params["stack"]["enc"])
        return layers.apply_norm(cfg, params["enc_final_norm"], x)

    # ----------------------------------------------------------------- loss

    def loss(self, params: Params, batch: dict) -> tuple[Array, dict]:
        logits, aux, h = self.forward(params, batch)
        ce = _next_token_ce(logits, batch["targets"])
        total = ce + aux
        metrics = {"ce": ce, "aux": aux}
        if self.cfg.mtp_depth:
            mtp_ce = self._mtp_loss(params, h, batch)
            total = total + 0.3 * mtp_ce
            metrics["mtp_ce"] = mtp_ce
        return total, metrics

    def _mtp_loss(self, params: Params, h: Array, batch: dict) -> Array:
        """DeepSeek-V3 multi-token prediction: one extra block predicts
        token t+2 from [h_t ; emb(target_t)]."""
        cfg = self.cfg
        emb = layers.embed(params["embedding"], batch["targets"])
        x = jnp.concatenate([h, emb.astype(h.dtype)], axis=-1) \
            @ params["mtp"]["proj"]
        x, _ = transformer.apply_layer(cfg, "attn_mlp",
                                       params["mtp"]["layer"], x)
        x = layers.apply_norm(cfg, params["mtp"]["norm"], x)
        logits = layers.unembed(cfg, params["embedding"], x[:, :-1])
        return _next_token_ce(logits, batch["targets"][:, 1:])

    # ------------------------------------------------------------ train step

    def train_step(self, params: Params, opt_state, batch: dict):
        """One optimizer step; with cfg.grad_accum > 1 the global batch is
        split into microbatches scanned with gradient accumulation (keeps
        activation memory ~1/A per chip — the standard large-model recipe)."""
        opt = self.init_optimizer()
        accum = self.cfg.grad_accum
        if accum <= 1:
            (loss_val, metrics), grads = jax.value_and_grad(
                self.loss, has_aux=True)(params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:]), batch)

            def micro_step(carry, mb):
                grads_acc, loss_acc = carry
                (lv, mets), g = jax.value_and_grad(
                    self.loss, has_aux=True)(params, mb)
                grads_acc = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), grads_acc, g)
                return (grads_acc, loss_acc + lv), mets

            zeros = jax.tree.map(jnp.zeros_like, params)
            (grads, loss_sum), mets = jax.lax.scan(
                micro_step, (zeros, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss_val = loss_sum / accum
            metrics = jax.tree.map(lambda m: m.mean(), mets)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = jax.tree.map(lambda w, u: w + u.astype(w.dtype),
                              params, updates)
        metrics = dict(metrics, loss=loss_val)
        return params, opt_state, metrics

    def train_step_deferred(self, mesh, params: Params, opt_state,
                            batch: dict):
        """§Perf optimization: gradient accumulation with DEFERRED data-
        parallel reduction.

        The plain ``train_step`` lets XLA make the grad-accum scan carry
        replicated across 'data', which inserts a full gradient all-reduce
        *inside every microbatch iteration* (visible in the baseline HLO
        census).  Here the data axes are manual (shard_map): each data
        shard accumulates its LOCAL grads across microbatches, and a single
        psum runs after the scan — collective volume drops by ~grad_accum×.
        The 'model' axis stays auto, so tensor-parallel sharding inside the
        loss is unchanged.
        """
        from jax.sharding import PartitionSpec as P
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        accum = max(self.cfg.grad_accum, 1)
        opt = self.init_optimizer()

        def per_shard(params, batch_shard):
            micro = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum)
                                    + x.shape[1:]), batch_shard)

            def micro_step(carry, mb):
                grads_acc, loss_acc = carry
                (lv, mets), g = jax.value_and_grad(
                    self.loss, has_aux=True)(params, mb)
                grads_acc = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), grads_acc, g)
                return (grads_acc, loss_acc + lv), mets

            # accumulate in f32 (also avoids XLA CPU's bf16 all-reduce
            # promotion crash when the deferred psum runs)
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), mets = jax.lax.scan(
                micro_step, (zeros, jnp.zeros((), jnp.float32)), micro)
            # THE deferred reduction: one psum after the accumulation
            grads = jax.lax.psum(grads, dp)
            loss_sum = jax.lax.psum(loss_sum, dp)
            mets = jax.lax.psum(mets, dp)
            return grads, loss_sum, mets

        n_dp = 1
        for a in dp:
            n_dp *= mesh.shape[a]
        batch_spec = jax.tree.map(lambda _: P(dp), batch)
        grads, loss_sum, mets = jax.shard_map(
            per_shard, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params), batch_spec),
            out_specs=(jax.tree.map(lambda _: P(), params), P(), P()),
            check_vma=False, axis_names=frozenset(dp))(params, batch)
        grads = jax.tree.map(lambda g: g / (accum * n_dp), grads)
        loss_val = loss_sum / (accum * n_dp)
        metrics = jax.tree.map(lambda m: m.mean() / n_dp, mets)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = jax.tree.map(lambda w, u: w + u.astype(w.dtype),
                              params, updates)
        metrics = dict(metrics, loss=loss_val)
        return params, opt_state, metrics

    # ------------------------------------------------------- prefill / decode

    def prefill(self, params: Params, batch: dict, max_len: int, *,
                rolling: bool = False) -> tuple[Array, Params]:
        """Forward over the prompt; returns (last-token logits, caches).

        The caches are *filled by re-running decode semantics* only in the
        serve path; for the assigned prefill shape we need the forward pass
        itself (logits + final hidden), which is what gets lowered.
        """
        logits, _, _ = self.forward(params, batch)
        caches = self.init_cache(batch["tokens"].shape[0], max_len,
                                 rolling=rolling)
        return logits[:, -1:], caches

    def init_cache(self, batch: int, max_len: int, *,
                   rolling: bool = False) -> Params:
        memory_len = AUDIO_MEMORY if self.cfg.is_encoder_decoder else 0
        return transformer.init_stack_cache(self.cfg, batch, max_len,
                                            rolling, memory_len)

    def decode_step(self, params: Params, caches: Params, tokens: Array,
                    *, rolling: bool = False) -> tuple[Array, Params]:
        """ONE new token (B, 1) against the caches."""
        cfg = self.cfg
        x = layers.embed(params["embedding"], tokens)
        x, caches = transformer.decode_stack(cfg, params["stack"], caches, x,
                                             rolling=rolling)
        x = layers.apply_norm(cfg, params["final_norm"], x)
        logits = layers.unembed(cfg, params["embedding"], x)
        return logits, caches

    # ------------------------------------------------------------ input specs

    def input_specs(self, shape: InputShape) -> dict:
        """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        dt = layers.dtype_of(cfg)
        sds = jax.ShapeDtypeStruct
        if cfg.is_encoder_decoder:
            if shape.step == "train":
                dec = s // DEC_FRACTION
                return {"frames": sds((b, s, cfg.d_model), dt),
                        "tokens": sds((b, dec), i32),
                        "targets": sds((b, dec), i32)}
            if shape.step == "prefill":
                return {"frames": sds((b, s, cfg.d_model), dt),
                        "tokens": sds((b, 1), i32),
                        "targets": sds((b, 1), i32)}
            return {"tokens": sds((b, 1), i32)}     # decode
        if cfg.arch_type == "vlm" and shape.step != "decode":
            npfx = cfg.frontend.num_embeddings
            text = s - npfx
            return {"tokens": sds((b, text), i32),
                    "targets": sds((b, text), i32),
                    "vision_embeds": sds((b, npfx, cfg.d_model), dt)}
        if shape.step == "decode":
            return {"tokens": sds((b, 1), i32)}
        return {"tokens": sds((b, s), i32),
                "targets": sds((b, s), i32)}

    def cache_specs(self, shape: InputShape, *, rolling: bool = False):
        return jax.eval_shape(
            lambda: self.init_cache(shape.global_batch, shape.seq_len,
                                    rolling=rolling))


def _next_token_ce(logits: Array, targets: Array) -> Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


def make_model(cfg: ModelConfig) -> Model:
    cfg.validate()
    return Model(cfg)
