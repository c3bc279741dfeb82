"""Unified model zoo: one API over six architecture families.

``build_model(cfg)`` returns a :class:`Model` with

* ``init(key)``                          — stacked-layer parameter pytree
* ``loss(params, batch)``                — next-token CE (+ MoE aux), f32
* ``forward(params, batch)``             — logits (train/prefill path)
* ``init_decode(batch_size)``            — per-layer decode state
* ``prefill(params, batch, state)``      — run the prompt, fill caches
* ``decode_step(params, state, tokens)`` — one token with cached state

Families:

* ``dense`` / ``vlm``  — llama-style GQA decoder (vlm prepends stub image
  embeddings); optional GELU-MLP variant (granite-34b / GPT-BigCode).
* ``moe``              — GQA decoder with top-k MoE FFN every
  ``moe_every``-th layer (scan over super-blocks when interleaved).
* ``ssm``              — RWKV-6 time-mix / channel-mix (attention-free).
* ``hybrid``           — Griffin repeating unit: ``pattern_recurrent``
  RG-LRU blocks + ``pattern_attn`` local-attention blocks.
* ``audio``            — whisper-style encoder-decoder over stub frame
  embeddings (the conv/mel frontend is out of scope per the assignment).

The repeated stack is applied with ``jax.lax.scan`` over layer-stacked
parameters (+ ``jax.checkpoint`` per step) so the HLO is depth-independent
and activation memory is one layer deep.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import attention, layers, moe, rglru, rwkv6

PyTree = Any


# ---------------------------------------------------------------------------
# Parameter initialization helpers
# ---------------------------------------------------------------------------

def _init_stacked(key, n: int, shapes: Dict[str, tuple], d_model: int,
                  dtype) -> Dict[str, jnp.ndarray]:
    out = {}
    ks = jax.random.split(key, len(shapes))
    scale = 0.02
    for (name, shape), k in zip(sorted(shapes.items()), ks):
        if name.endswith("_norm") or name in ("ln_w", "ln_b"):
            out[name] = jnp.zeros((n,) + shape, dtype)
        elif name.startswith("mix_") or name.startswith("cmix_"):
            out[name] = jnp.full((n,) + shape, 0.5, dtype)
        elif name == "decay_base":
            out[name] = jnp.full((n,) + shape, -1.0, dtype)
        elif name == "lam":
            # RG-LRU Λ init so a ∈ (0.9, 0.999) at r = 0.5 (Griffin §2.4)
            out[name] = jnp.full((n,) + shape, 0.7, dtype)
        elif name == "bonus":
            out[name] = jnp.zeros((n,) + shape, dtype)
        elif name.startswith("b_"):
            out[name] = jnp.zeros((n,) + shape, dtype)
        else:
            out[name] = layers.normal(k, (n,) + shape, scale, dtype)
    return out


def _block_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    """Per-layer parameter shapes for one *attention + FFN* block."""
    d, hd = cfg.d_model, cfg.head_dim
    qh, kvh = cfg.num_heads, cfg.num_kv_heads
    s = {
        "attn_norm": (d,),
        "wq": (d, qh * hd), "wk": (d, kvh * hd), "wv": (d, kvh * hd),
        "wo": (qh * hd, d),
        "ffn_norm": (d,),
    }
    s.update(_ffn_shapes(cfg))
    return s


def _ffn_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.ffn == "swiglu":
        return {"wg": (d, f), "wu": (d, f), "wd": (f, d)}
    return {"wi": (d, f), "b_i": (f,), "wo2": (f, d), "b_o": (d,)}


def _moe_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = {"router": (d, e), "ewg": (e, d, f), "ewu": (e, d, f),
         "ewd": (e, f, d)}
    if cfg.shared_expert:
        s.update({"swg": (d, f), "swu": (d, f), "swd": (f, d)})
    return s


def _recurrent_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    d = cfg.d_model
    return {
        "rec_norm": (d,),
        "wx": (d, d), "wgate": (d, d), "w_ri": (d, 2 * d),
        "conv_w": (cfg.conv_width, d), "lam": (d,), "w_out": (d, d),
        "ffn_norm": (d,),
        **_ffn_shapes(cfg),
    }


# ---------------------------------------------------------------------------
# Block apply functions (one layer; layer params already sliced)
# ---------------------------------------------------------------------------

def _ffn_apply(cfg, p, x):
    with jax.named_scope("ffn"):
        if cfg.ffn == "swiglu":
            return layers.swiglu(x, p["wg"], p["wu"], p["wd"])
        return layers.gelu_mlp(x, p["wi"], p["b_i"], p["wo2"], p["b_o"])


def _attn_apply(cfg, p, x, positions, *, window: int = 0,
                chunked: bool = False):
    with jax.named_scope("attention"):
        b, s, d = x.shape
        h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        xn = layers.rms_norm(x, p["attn_norm"], cfg.norm_eps)
        q = (xn @ p["wq"]).reshape(b, s, h, hd)
        k = (xn @ p["wk"]).reshape(b, s, kvh, hd)
        v = (xn @ p["wv"]).reshape(b, s, kvh, hd)
        q = layers.apply_rope(q, positions, cfg.rope_theta)
        k = layers.apply_rope(k, positions, cfg.rope_theta)
        if chunked and s > 1024:
            o = attention.attend_chunked(q, k, v, causal=True,
                                         window=window)
        else:
            o = attention.attend(q, k, v, causal=True, window=window)
        return x + o.reshape(b, s, h * hd) @ p["wo"]


def _attn_block(cfg, p, x, positions, *, window: int = 0,
                chunked: bool = False):
    x = _attn_apply(cfg, p, x, positions, window=window, chunked=chunked)
    xn = layers.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    return x + _ffn_apply(cfg, p, xn)


def _attn_decode(cfg, p, x, k_cache, v_cache, length, *, window: int = 0):
    """One-token attention against a cache. x: (B, 1, D)."""
    b, _, d = x.shape
    h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    xn = layers.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    pos = length[None]  # absolute position of this token
    q = layers.apply_rope((xn @ p["wq"]).reshape(b, 1, h, hd), pos,
                          cfg.rope_theta)
    k = layers.apply_rope((xn @ p["wk"]).reshape(b, 1, kvh, hd), pos,
                          cfg.rope_theta)
    v = (xn @ p["wv"]).reshape(b, 1, kvh, hd)
    cache = attention.KVCache(k_cache, v_cache, length)
    cache = attention.cache_update(cache, k, v)
    o = attention.decode_attend(q, cache, window=window)
    x = x + o.reshape(b, 1, h * hd) @ p["wo"]
    xn = layers.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    x = x + _ffn_apply(cfg, p, xn)
    return x, cache.k, cache.v


def _moe_ffn_apply(cfg, p, xn, *, expert_parallel: bool = False,
                   dp_axes=None, weight_mode: str = "fsdp"):
    mp = {"router": p["router"], "wg": p["ewg"], "wu": p["ewu"],
          "wd": p["ewd"]}
    if cfg.shared_expert:
        mp.update({"shared_wg": p["swg"], "shared_wu": p["swu"],
                   "shared_wd": p["swd"]})
    fn = moe.moe_ffn_sharded if expert_parallel else moe.moe_ffn
    kw = dict(num_experts=cfg.num_experts, k=cfg.experts_per_token,
              capacity_factor=cfg.capacity_factor)
    if expert_parallel:
        kw["dp_axes"] = dp_axes
        kw["weight_mode"] = weight_mode
    return fn(xn, mp, **kw)


def _moe_block(cfg, p, x, positions, *, chunked: bool = False,
               expert_parallel: bool = False, dp_axes=None,
               weight_mode: str = "fsdp"):
    x = _attn_apply(cfg, p, x, positions, chunked=chunked)
    xn = layers.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    out = _moe_ffn_apply(cfg, p, xn, expert_parallel=expert_parallel,
                         dp_axes=dp_axes, weight_mode=weight_mode)
    return x + out.y, out.aux_loss


def _recurrent_block(cfg, p, x, *, h0=None, conv_state=None,
                     decode: bool = False):
    """Griffin recurrent block. Returns (x, h_last, conv_state)."""
    xn = layers.rms_norm(x, p["rec_norm"], cfg.norm_eps)
    branch = xn @ p["wx"]
    gate = jax.nn.gelu(xn @ p["wgate"], approximate=True)
    branch, conv_state = rglru.temporal_conv(branch, p["conv_w"], conv_state)
    ri = jax.nn.sigmoid(branch @ p["w_ri"])
    r, i = jnp.split(ri, 2, axis=-1)
    if decode:
        y, h = rglru.rg_lru_step(branch[:, 0], r[:, 0], i[:, 0], p["lam"],
                                 h0)
        y = y[:, None]
    else:
        y, h = rglru.rg_lru(branch, r, i, p["lam"], h0)
    x = x + (y * gate) @ p["w_out"]
    xn = layers.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    return x + _ffn_apply(cfg, p, xn), h, conv_state


def _rwkv_shapes(cfg: ModelConfig) -> Dict[str, tuple]:
    d, f, h = cfg.d_model, cfg.d_ff, cfg.rwkv_heads
    s = {k: v for k, v in
         rwkv6.time_mix_params_shapes(d, h).items()}
    s.update({"tm_norm": (d,), "cm_norm": (d,),
              "cmix_k": (d,), "cmix_r": (d,),
              "ck": (d, f), "cv": (f, d), "cr": (d, d)})
    return s


def _rwkv_block(cfg, p, x, state: rwkv6.RWKVState, cm_shift, *,
                decode: bool = False):
    xn = layers.rms_norm(x, p["tm_norm"], cfg.norm_eps)
    y, new_state = rwkv6.time_mix(p, xn, state, cfg.rwkv_heads,
                                  decode=decode)
    x = x + y
    xn = layers.rms_norm(x, p["cm_norm"], cfg.norm_eps)
    y, new_cm_shift = rwkv6.channel_mix(p, xn, cm_shift)
    return x + y, new_state, new_cm_shift


# ---------------------------------------------------------------------------
# Decode state
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    """Per-family decode state; unused fields are empty arrays."""
    length: jnp.ndarray                 # () int32 — tokens written so far
    kv_k: PyTree                        # stacked (n, B, C, Hkv, hd) or {}
    kv_v: PyTree
    rec_h: PyTree                       # rglru hidden / rwkv wkv state
    rec_conv: PyTree                    # conv context / rwkv shift states
    cross_k: PyTree                     # whisper cross-attn keys
    cross_v: PyTree


def _empty():
    return jnp.zeros((0,), jnp.float32)


# ---------------------------------------------------------------------------
# The Model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    decode_window: int = 0    # 0 = full cache; >0 = ring buffer (long ctx)
    # mesh axes the batch dim shards over (None = no constraint; set by the
    # launch layer).  Used for with_sharding_constraint on activations that
    # XLA's propagation otherwise replicates (notably the logits' vocab dim).
    dp_axes: Optional[tuple] = None
    shard_logits: bool = True
    # launch-layer hook: (leaf_name, per-layer shape) -> PartitionSpec for
    # scan-sliced layer params; see launch.sharding.layer_pspec_fn.
    layer_pspec_fn: Optional[Any] = None
    # TP axis for activation/vocab sharding between layers; None in pure-
    # FSDP mode (batch over every mesh axis, no tensor parallelism).
    act_tp: Optional[str] = "model"
    # run MoE FFNs through the shard_map expert-parallel path (requires the
    # production mesh; the pjit scatter formulation replicates the dispatch
    # buffer per device — see repro.models.moe).
    expert_parallel: bool = False
    # "fsdp" (train) or "stationary" (decode weight-stationary TP)
    moe_weight_mode: str = "fsdp"

    def _wsc(self, x, *spec):
        if self.dp_axes is None:
            return x
        return jax.lax.with_sharding_constraint(x, P(*spec))

    def _act_constraint(self, x):
        """Pin sequence activations to (batch@data, seq, d_model) — without
        this, XLA's propagation can fall into a weight-stationary layout
        that replicates the batch across the FSDP axis (observed: 147 GiB
        temp for llama3-8b train_4k).  Applied after the embedding and to
        every layer-scan carry."""
        if self.dp_axes is None:
            return x
        dp = self.dp_axes if x.shape[0] > 1 else None
        # d_model additionally shards over the TP axis between layers
        # (Megatron sequence/activation sharding): the layer-scan's saved
        # carry stacks shrink by the TP degree; XLA inserts the per-layer
        # all-gather/reduce-scatter pair.  act_tp=None (pure FSDP): batch
        # carries all parallelism, activations stay whole.
        return jax.lax.with_sharding_constraint(x, P(dp, None, self.act_tp))

    def _logits_constraint(self, logits):
        if self.dp_axes is None or not self.shard_logits:
            return logits
        dp = self.dp_axes if logits.shape[0] > 1 else None
        return jax.lax.with_sharding_constraint(
            logits, P(dp, None, self.act_tp))

    def _unembed(self, params, x):
        """Tied unembedding with an explicit sharded contraction: the
        table is re-laid-out (vocab stays on `model`, its d_model dim is
        gathered from the FSDP axis) so each device computes its own
        (batch-shard, vocab-shard) logits block — XLA's default propagation
        otherwise replicates the vocab dim of the logits."""
        table = params["embed"]
        if self.dp_axes is not None and self.shard_logits \
                and self.act_tp is not None:
            table = jax.lax.with_sharding_constraint(
                table, P(self.act_tp, None))
        with jax.named_scope("unembed"):
            return self._logits_constraint(layers.unembed(x, table))

    # -- init ---------------------------------------------------------------

    def init(self, key) -> PyTree:
        cfg = self.cfg
        dt = cfg.pdtype
        k_embed, k_blocks, k_extra = jax.random.split(key, 3)
        params: Dict[str, Any] = {
            "embed": layers.normal(k_embed, (cfg.padded_vocab, cfg.d_model),
                                   0.02, dt),
            "final_norm": jnp.zeros((cfg.d_model,), dt),
        }
        fam = cfg.family
        if fam in ("dense", "vlm"):
            params["blocks"] = _init_stacked(
                k_blocks, cfg.num_layers, _block_shapes(cfg), cfg.d_model, dt)
        elif fam == "moe":
            if cfg.moe_every == 1:
                shapes = dict(_block_shapes(cfg))
                for key_ in _ffn_shapes(cfg):
                    shapes.pop(key_)
                shapes.update(_moe_shapes(cfg))
                params["blocks"] = _init_stacked(
                    k_blocks, cfg.num_layers, shapes, cfg.d_model, dt)
            else:
                # super-block = (dense block, moe block)
                n_units = cfg.num_layers // cfg.moe_every
                dense_shapes = {f"d_{k}": v
                                for k, v in _block_shapes(cfg).items()}
                moe_shapes = dict(_block_shapes(cfg))
                for key_ in _ffn_shapes(cfg):
                    moe_shapes.pop(key_)
                moe_shapes.update(_moe_shapes(cfg))
                moe_shapes = {f"m_{k}": v for k, v in moe_shapes.items()}
                params["blocks"] = _init_stacked(
                    k_blocks, n_units, {**dense_shapes, **moe_shapes},
                    cfg.d_model, dt)
        elif fam == "ssm":
            params["blocks"] = _init_stacked(
                k_blocks, cfg.num_layers, _rwkv_shapes(cfg), cfg.d_model, dt)
        elif fam == "hybrid":
            unit = cfg.pattern_recurrent + cfg.pattern_attn
            n_units = cfg.num_layers // unit
            tail = cfg.num_layers - n_units * unit
            shapes = {}
            for r in range(cfg.pattern_recurrent):
                shapes.update({f"r{r}_{k}": v
                               for k, v in _recurrent_shapes(cfg).items()})
            for a in range(cfg.pattern_attn):
                shapes.update({f"a{a}_{k}": v
                               for k, v in _block_shapes(cfg).items()})
            params["blocks"] = _init_stacked(
                k_blocks, n_units, shapes, cfg.d_model, dt)
            if tail:
                params["tail"] = _init_stacked(
                    k_extra, tail, _recurrent_shapes(cfg), cfg.d_model, dt)
        elif fam == "audio":
            # decoder blocks with cross-attention
            dec_shapes = dict(_block_shapes(cfg))
            dec_shapes.update({
                "xattn_norm": (cfg.d_model,),
                "xwq": (cfg.d_model, cfg.num_heads * cfg.head_dim),
                "xwk": (cfg.d_model, cfg.num_kv_heads * cfg.head_dim),
                "xwv": (cfg.d_model, cfg.num_kv_heads * cfg.head_dim),
                "xwo": (cfg.num_heads * cfg.head_dim, cfg.d_model),
            })
            params["blocks"] = _init_stacked(
                k_blocks, cfg.num_layers, dec_shapes, cfg.d_model, dt)
            enc_cfg = dataclasses.replace(cfg, ffn="gelu")
            params["encoder"] = _init_stacked(
                k_extra, cfg.encoder_layers, _block_shapes(enc_cfg),
                cfg.d_model, dt)
            params["enc_final_norm"] = jnp.zeros((cfg.d_model,), dt)
        else:
            raise ValueError(f"unknown family {fam}")
        if fam == "vlm":
            # stub projector for the (already-encoded) image patches
            params["img_proj"] = layers.normal(
                k_extra, (cfg.d_model, cfg.d_model), 0.02, dt)
        return params

    # -- shared helpers -----------------------------------------------------

    def _cast(self, p):
        """Per-layer param prep inside scan bodies: (1) re-pin the sliced
        leaf to its sharded spec (keeps the FSDP all-gather inside the
        loop), (2) cast to activation dtype (keeps the bf16 copy one layer
        deep; norm weights are re-upcast inside rms_norm)."""
        ad = self.cfg.adtype
        if self.layer_pspec_fn is not None:
            def pin(path, w):
                name = str(getattr(path[-1], "key", path[-1]))
                spec = self.layer_pspec_fn(name, w.shape)
                return jax.lax.with_sharding_constraint(w, spec).astype(ad)
            return jax.tree_util.tree_map_with_path(pin, p)
        return jax.tree.map(lambda w: w.astype(ad), p)

    def _scan_blocks(self, body, x, blocks, extra=None, unroll: bool = False):
        """checkpointed scan over stacked layer params."""
        def cast_body(carry, layer_p):
            out = body(carry, self._cast(layer_p))
            if isinstance(out, tuple):
                return (self._act_constraint(out[0]),) + out[1:]
            return self._act_constraint(out)

        ckpt = jax.checkpoint(cast_body,
                              policy=jax.checkpoint_policies.nothing_saveable)

        def step(carry, layer_p):
            return ckpt(carry, layer_p), None

        carry, _ = jax.lax.scan(step, x, blocks)
        return carry

    # -- forward (train / prefill) ------------------------------------------

    def forward(self, params, batch) -> jnp.ndarray:
        """Full-sequence logits (MoE aux loss discarded)."""
        return self.forward_with_aux(params, batch)[0]

    def forward_with_aux(self, params, batch):
        """Full-sequence logits + auxiliary losses.  batch: dict with
        "tokens" (B, S_text) and family-specific stub embeddings (see
        launch/specs.py)."""
        cfg = self.cfg
        ad = cfg.adtype
        tokens = batch["tokens"]
        x = layers.embed(tokens, params["embed"]).astype(ad)
        aux: list = []

        if cfg.family == "vlm":
            img = batch["img_embeds"].astype(ad) @ params["img_proj"].astype(ad)
            x = jnp.concatenate([img, x], axis=1)
        x = self._act_constraint(x)
        b, s, _ = x.shape
        positions = jnp.arange(s)[None, :]
        chunked = s > 1024

        fam = cfg.family
        if fam in ("dense", "vlm"):
            def body(h, p):
                return _attn_block(cfg, p, h, positions, chunked=chunked)
            x = self._scan_blocks(body, x, params["blocks"])
        elif fam == "moe":
            aux_total = jnp.zeros((), jnp.float32)
            if cfg.moe_every == 1:
                def body(carry, p):
                    h, a = carry
                    h, al = _moe_block(cfg, p, h, positions, chunked=chunked,
                                       expert_parallel=self.expert_parallel,
                                       dp_axes=self.dp_axes,
                                       weight_mode=self.moe_weight_mode)
                    return h, a + al
                (x, aux_total) = self._scan_blocks(
                    body, (x, aux_total), params["blocks"])
            else:
                def body(carry, p):
                    h, a = carry
                    dp = {k[2:]: v for k, v in p.items()
                          if k.startswith("d_")}
                    mp = {k[2:]: v for k, v in p.items()
                          if k.startswith("m_")}
                    h = _attn_block(cfg, dp, h, positions, chunked=chunked)
                    h, al = _moe_block(cfg, mp, h, positions, chunked=chunked,
                                       expert_parallel=self.expert_parallel,
                                       dp_axes=self.dp_axes,
                                       weight_mode=self.moe_weight_mode)
                    return h, a + al
                (x, aux_total) = self._scan_blocks(
                    body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
            aux.append(aux_total)
        elif fam == "ssm":
            h0 = jnp.zeros((b, cfg.rwkv_heads,
                            cfg.d_model // cfg.rwkv_heads,
                            cfg.d_model // cfg.rwkv_heads), jnp.float32)
            shift0 = jnp.zeros((b, cfg.d_model), ad)

            def body(h, p):
                st = rwkv6.RWKVState(wkv=h0, shift=shift0)
                out, _, _ = _rwkv_block(cfg, p, h, st, shift0)
                return out
            x = self._scan_blocks(body, x, params["blocks"])
        elif fam == "hybrid":
            def body(h, p):
                for r in range(cfg.pattern_recurrent):
                    rp = {k[len(f"r{r}_"):]: v for k, v in p.items()
                          if k.startswith(f"r{r}_")}
                    h, _, _ = _recurrent_block(cfg, rp, h)
                for a_i in range(cfg.pattern_attn):
                    ap = {k[len(f"a{a_i}_"):]: v for k, v in p.items()
                          if k.startswith(f"a{a_i}_")}
                    h = _attn_block(cfg, ap, h, positions,
                                    window=cfg.local_window, chunked=chunked)
                return h
            x = self._scan_blocks(body, x, params["blocks"])
            if "tail" in params:
                def tbody(h, p):
                    h, _, _ = _recurrent_block(cfg, p, h)
                    return h
                x = self._scan_blocks(tbody, x, params["tail"])
        elif fam == "audio":
            enc = self._encode(params, batch)
            def body(h, p):
                h = _attn_apply(cfg, p, h, positions, chunked=chunked)
                h = self._cross_attn(p, h, enc)
                hn = layers.rms_norm(h, p["ffn_norm"], cfg.norm_eps)
                return h + _ffn_apply(cfg, p, hn)
            x = self._scan_blocks(body, x, params["blocks"])

        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x)
        if cfg.family == "vlm":
            logits = logits[:, cfg.num_image_tokens:]
        return logits, aux

    def _encode(self, params, batch):
        cfg = self.cfg
        ad = cfg.adtype
        frames = batch["frame_embeds"].astype(ad)      # (B, S_enc, D)
        s = frames.shape[1]
        pos = jnp.arange(s)[None, :]
        # sinusoidal positions on the stub embeddings
        half = cfg.d_model // 2
        freqs = jnp.exp(-jnp.arange(half) / half * jnp.log(10000.0))
        ang = pos[..., None] * freqs
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(ad)
        x = frames + pe
        enc_cfg = dataclasses.replace(cfg, ffn="gelu")

        def body(h, p):
            hn = layers.rms_norm(h, p["attn_norm"], cfg.norm_eps)
            b, ss, _ = h.shape
            q = (hn @ p["wq"]).reshape(b, ss, cfg.num_heads, cfg.head_dim)
            k = (hn @ p["wk"]).reshape(b, ss, cfg.num_kv_heads, cfg.head_dim)
            v = (hn @ p["wv"]).reshape(b, ss, cfg.num_kv_heads, cfg.head_dim)
            o = attention.attend(q, k, v, causal=False)
            h = h + o.reshape(b, ss, -1) @ p["wo"]
            hn = layers.rms_norm(h, p["ffn_norm"], cfg.norm_eps)
            return h + _ffn_apply(enc_cfg, p, hn)

        x = self._scan_blocks(body, x, params["encoder"])
        return layers.rms_norm(x, params["enc_final_norm"], cfg.norm_eps)

    def _cross_attn(self, p, x, enc):
        cfg = self.cfg
        b, s, _ = x.shape
        se = enc.shape[1]
        xn = layers.rms_norm(x, p["xattn_norm"], cfg.norm_eps)
        q = (xn @ p["xwq"]).reshape(b, s, cfg.num_heads, cfg.head_dim)
        k = (enc @ p["xwk"]).reshape(b, se, cfg.num_kv_heads, cfg.head_dim)
        v = (enc @ p["xwv"]).reshape(b, se, cfg.num_kv_heads, cfg.head_dim)
        o = attention.attend(q, k, v, causal=False)
        return x + o.reshape(b, s, -1) @ p["xwo"]

    # -- loss ----------------------------------------------------------------

    def loss(self, params, batch) -> jnp.ndarray:
        cfg = self.cfg
        logits, aux = self.forward_with_aux(params, batch)
        tokens = batch["tokens"]
        ce = layers.softmax_cross_entropy(logits[:, :-1], tokens[:, 1:])
        if aux:
            ce = ce + cfg.router_aux_weight * aux[0] / cfg.num_layers
        return ce

    # -- decode ---------------------------------------------------------------

    def _n_attn_layers(self):
        cfg = self.cfg
        if cfg.family in ("dense", "vlm", "audio"):
            return cfg.num_layers
        if cfg.family == "moe":
            return cfg.num_layers
        if cfg.family == "hybrid":
            unit = cfg.pattern_recurrent + cfg.pattern_attn
            return (cfg.num_layers // unit) * cfg.pattern_attn
        return 0

    def init_decode(self, batch_size: int, max_len: int) -> DecodeState:
        """Allocate caches.  ``decode_window`` > 0 ⇒ ring buffer of that
        size (sub-quadratic long-context variant); hybrids use their local
        window; ssm needs O(1) state only."""
        cfg = self.cfg
        n_attn = self._n_attn_layers()
        if cfg.family == "hybrid":
            cap = min(cfg.local_window, max_len)
        elif self.decode_window:
            cap = min(self.decode_window, max_len)
        else:
            cap = max_len
        dt = cfg.adtype
        kv_shape = (n_attn, batch_size, cap, cfg.num_kv_heads, cfg.head_dim)
        kv_k = jnp.zeros(kv_shape, dt) if n_attn else _empty()
        kv_v = jnp.zeros(kv_shape, dt) if n_attn else _empty()
        rec_h, rec_conv = _empty(), _empty()
        if cfg.family == "ssm":
            hd = cfg.d_model // cfg.rwkv_heads
            rec_h = jnp.zeros((cfg.num_layers, batch_size, cfg.rwkv_heads,
                               hd, hd), jnp.float32)
            # shift states: one for time-mix, one for channel-mix
            rec_conv = jnp.zeros((cfg.num_layers, 2, batch_size,
                                  cfg.d_model), dt)
        if cfg.family == "hybrid":
            n_rec = cfg.num_layers - self._n_attn_layers()
            rec_h = jnp.zeros((n_rec, batch_size, cfg.d_model), jnp.float32)
            rec_conv = jnp.zeros((n_rec, batch_size, cfg.conv_width - 1,
                                  cfg.d_model), dt)
        cross_k = cross_v = _empty()
        if cfg.family == "audio":
            cshape = (cfg.num_layers, batch_size, cfg.encoder_seq,
                      cfg.num_kv_heads, cfg.head_dim)
            cross_k = jnp.zeros(cshape, dt)
            cross_v = jnp.zeros(cshape, dt)
        return DecodeState(length=jnp.zeros((), jnp.int32), kv_k=kv_k,
                           kv_v=kv_v, rec_h=rec_h, rec_conv=rec_conv,
                           cross_k=cross_k, cross_v=cross_v)

    def precompute_cross(self, params, batch, state: DecodeState):
        """Whisper: run the encoder once, cache per-layer cross K/V."""
        cfg = self.cfg
        enc = self._encode(params, batch)                  # (B, Se, D)
        b, se, _ = enc.shape

        def per_layer(p):
            p = self._cast(p)
            k = (enc @ p["xwk"]).reshape(b, se, cfg.num_kv_heads,
                                         cfg.head_dim)
            v = (enc @ p["xwv"]).reshape(b, se, cfg.num_kv_heads,
                                         cfg.head_dim)
            return k.astype(cfg.adtype), v.astype(cfg.adtype)

        ks, vs = jax.vmap(per_layer)(params["blocks"])
        return state._replace(cross_k=ks, cross_v=vs)

    def decode_step(self, params, state: DecodeState, tokens):
        """One token for every sequence in the batch. tokens: (B, 1)."""
        cfg = self.cfg
        ad = cfg.adtype
        x = layers.embed(tokens, params["embed"]).astype(ad)   # (B, 1, D)
        length = state.length
        fam = cfg.family
        window = self.decode_window
        if fam == "hybrid":
            window = cfg.local_window

        new_state = state
        if fam in ("dense", "vlm"):
            def body(h, xs):
                p, kc, vc = xs
                p = self._cast(p)
                h, k2, v2 = _attn_decode(cfg, p, h, kc, vc, length,
                                         window=window)
                return h, (k2, v2)
            x, (kk, vv) = jax.lax.scan(
                body, x, (params["blocks"], state.kv_k, state.kv_v))
            new_state = new_state._replace(kv_k=kk, kv_v=vv)
        elif fam == "moe":
            positions = None
            if cfg.moe_every == 1:
                def body(h, xs):
                    p, kc, vc = xs
                    p = self._cast(p)
                    h, k2, v2 = self._moe_decode(p, h, kc, vc, length,
                                                 window=window)
                    return h, (k2, v2)
                x, (kk, vv) = jax.lax.scan(
                    body, x, (params["blocks"], state.kv_k, state.kv_v))
            else:
                n_units = cfg.num_layers // cfg.moe_every
                kd = state.kv_k.reshape((n_units, 2) + state.kv_k.shape[1:])
                vd = state.kv_v.reshape((n_units, 2) + state.kv_v.shape[1:])

                def body(h, xs):
                    p, kc, vc = xs
                    p = self._cast(p)
                    dp = {k[2:]: v for k, v in p.items()
                          if k.startswith("d_")}
                    mp = {k[2:]: v for k, v in p.items()
                          if k.startswith("m_")}
                    h, k1, v1 = _attn_decode(cfg, dp, h, kc[0], vc[0],
                                             length, window=window)
                    h, k2, v2 = self._moe_decode(mp, h, kc[1], vc[1],
                                                 length, window=window)
                    return h, (jnp.stack([k1, k2]), jnp.stack([v1, v2]))
                x, (kk, vv) = jax.lax.scan(body, x, (params["blocks"],
                                                     kd, vd))
                kk = kk.reshape(state.kv_k.shape)
                vv = vv.reshape(state.kv_v.shape)
            new_state = new_state._replace(kv_k=kk, kv_v=vv)
        elif fam == "ssm":
            def body(h, xs):
                p, wkv, shifts = xs
                p = self._cast(p)
                st = rwkv6.RWKVState(wkv=wkv, shift=shifts[0])
                h2, st2, cm2 = _rwkv_block(cfg, p, h, st, shifts[1],
                                           decode=True)
                return h2, (st2.wkv, jnp.stack([st2.shift, cm2]))
            x, (wkvs, shifts) = jax.lax.scan(
                body, x, (params["blocks"], state.rec_h, state.rec_conv))
            new_state = new_state._replace(rec_h=wkvs, rec_conv=shifts)
        elif fam == "hybrid":
            unit = cfg.pattern_recurrent + cfg.pattern_attn
            n_units = cfg.num_layers // unit
            pr, pa = cfg.pattern_recurrent, cfg.pattern_attn
            rh = state.rec_h[:n_units * pr].reshape(
                (n_units, pr) + state.rec_h.shape[1:])
            rc = state.rec_conv[:n_units * pr].reshape(
                (n_units, pr) + state.rec_conv.shape[1:])
            ka = state.kv_k.reshape((n_units, pa) + state.kv_k.shape[1:])
            va = state.kv_v.reshape((n_units, pa) + state.kv_v.shape[1:])

            def body(h, xs):
                p, rhs, rcs, kcs, vcs = xs
                p = self._cast(p)
                rh_out, rc_out, k_out, v_out = [], [], [], []
                for r in range(pr):
                    rp = {k[len(f"r{r}_"):]: v for k, v in p.items()
                          if k.startswith(f"r{r}_")}
                    h, hh, cc = _recurrent_block(cfg, rp, h, h0=rhs[r],
                                                 conv_state=rcs[r],
                                                 decode=True)
                    rh_out.append(hh); rc_out.append(cc)
                for a_i in range(pa):
                    ap = {k[len(f"a{a_i}_"):]: v for k, v in p.items()
                          if k.startswith(f"a{a_i}_")}
                    h, k2, v2 = _attn_decode(cfg, ap, h, kcs[a_i], vcs[a_i],
                                             length, window=cfg.local_window)
                    k_out.append(k2); v_out.append(v2)
                return h, (jnp.stack(rh_out), jnp.stack(rc_out),
                           jnp.stack(k_out), jnp.stack(v_out))

            x, (rh2, rc2, ka2, va2) = jax.lax.scan(
                body, x, (params["blocks"], rh, rc, ka, va))
            rh2 = rh2.reshape(state.rec_h[:n_units * pr].shape)
            rc2 = rc2.reshape(state.rec_conv[:n_units * pr].shape)
            new_rec_h, new_rec_conv = rh2, rc2
            if "tail" in params:
                def tbody(h, xs):
                    p, hh, cc = xs
                    p = self._cast(p)
                    h, h2, c2 = _recurrent_block(cfg, p, h, h0=hh,
                                                 conv_state=cc, decode=True)
                    return h, (h2, c2)
                x, (th, tc) = jax.lax.scan(
                    tbody, x, (params["tail"], state.rec_h[n_units * pr:],
                               state.rec_conv[n_units * pr:]))
                new_rec_h = jnp.concatenate([rh2, th])
                new_rec_conv = jnp.concatenate([rc2, tc])
            new_state = new_state._replace(
                rec_h=new_rec_h, rec_conv=new_rec_conv,
                kv_k=ka2.reshape(state.kv_k.shape),
                kv_v=va2.reshape(state.kv_v.shape))
        elif fam == "audio":
            def body(h, xs):
                p, kc, vc, xk, xv = xs
                p = self._cast(p)
                hn = layers.rms_norm(h, p["attn_norm"], cfg.norm_eps)
                b = h.shape[0]
                q = layers.apply_rope(
                    (hn @ p["wq"]).reshape(b, 1, cfg.num_heads, cfg.head_dim),
                    length[None], cfg.rope_theta)
                k = layers.apply_rope(
                    (hn @ p["wk"]).reshape(b, 1, cfg.num_kv_heads,
                                           cfg.head_dim), length[None],
                    cfg.rope_theta)
                v = (hn @ p["wv"]).reshape(b, 1, cfg.num_kv_heads,
                                           cfg.head_dim)
                cache = attention.KVCache(kc, vc, length)
                cache = attention.cache_update(cache, k, v)
                o = attention.decode_attend(q, cache, window=window)
                h = h + o.reshape(b, 1, -1) @ p["wo"]
                # cross attention against the precomputed encoder K/V
                hn = layers.rms_norm(h, p["xattn_norm"], cfg.norm_eps)
                q = (hn @ p["xwq"]).reshape(b, 1, cfg.num_heads, cfg.head_dim)
                xc = attention.KVCache(xk, xv,
                                       jnp.asarray(xk.shape[1], jnp.int32))
                o = attention.decode_attend(q, xc)
                h = h + o.reshape(b, 1, -1) @ p["xwo"]
                hn = layers.rms_norm(h, p["ffn_norm"], cfg.norm_eps)
                h = h + _ffn_apply(cfg, p, hn)
                return h, (cache.k, cache.v)
            x, (kk, vv) = jax.lax.scan(
                body, x, (params["blocks"], state.kv_k, state.kv_v,
                          state.cross_k, state.cross_v))
            new_state = new_state._replace(kv_k=kk, kv_v=vv)

        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x)
        return logits, new_state._replace(length=length + 1)

    def _moe_decode(self, p, x, k_cache, v_cache, length, *, window=0):
        cfg = self.cfg
        x, k2, v2 = self._attn_decode_only(p, x, k_cache, v_cache, length,
                                           window)
        xn = layers.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        out = _moe_ffn_apply(cfg, p, xn,
                             expert_parallel=self.expert_parallel,
                             dp_axes=self.dp_axes,
                             weight_mode=self.moe_weight_mode)
        return x + out.y, k2, v2

    def _attn_decode_only(self, p, x, k_cache, v_cache, length, window):
        cfg = self.cfg
        b = x.shape[0]
        h, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        xn = layers.rms_norm(x, p["attn_norm"], cfg.norm_eps)
        pos = length[None]
        q = layers.apply_rope((xn @ p["wq"]).reshape(b, 1, h, hd), pos,
                              cfg.rope_theta)
        k = layers.apply_rope((xn @ p["wk"]).reshape(b, 1, kvh, hd), pos,
                              cfg.rope_theta)
        v = (xn @ p["wv"]).reshape(b, 1, kvh, hd)
        cache = attention.KVCache(k_cache, v_cache, length)
        cache = attention.cache_update(cache, k, v)
        o = attention.decode_attend(q, cache, window=window)
        return x + o.reshape(b, 1, h * hd) @ p["wo"], cache.k, cache.v


def build_model(cfg: ModelConfig, *, decode_window: int = 0,
                dp_axes: Optional[tuple] = None,
                shard_logits: bool = True,
                layer_pspec_fn=None,
                expert_parallel: bool = False,
                act_tp: Optional[str] = "model") -> Model:
    return Model(cfg=cfg, decode_window=decode_window, dp_axes=dp_axes,
                 shard_logits=shard_logits, layer_pspec_fn=layer_pspec_fn,
                 expert_parallel=expert_parallel, act_tp=act_tp)
