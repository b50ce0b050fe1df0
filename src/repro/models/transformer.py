"""Model assembly: segment plan, scan-over-layers, train/prefill/decode.

Every architecture compiles to a list of *segments* -- homogeneous runs of
layers executed with ``jax.lax.scan`` over stacked parameters (bounded HLO
size even for the 61-layer DeepSeek config), plus occasional "single"
layers where the stack is heterogeneous (hymba's three global-attention
layers, xLSTM's sLSTM blocks).

Modes:
* ``train_loss``  : full-sequence forward + causal CE (+ MoE aux loss)
* ``prefill``     : forward that also builds the per-layer caches
* ``decode_step`` : one token in, one logits row out, caches updated

Cache pytree mirrors the segment list; scanned segments stack their cache
leaves on a leading layer axis.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import attention as A
from . import layers as L
from . import moe as M
from . import ssm as S


@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str        # "scan" | "single"
    n: int
    mixer: str       # "attn" | "mla" | "hybrid" | "mlstm" | "slstm"
    ffn: str         # "swiglu" | "moe" | "none"
    window: int = 0
    cross: bool = False


def build_plan(cfg: ArchConfig) -> list:
    if cfg.family == "ssm":                     # xlstm: 5 mLSTM + 1 sLSTM per group
        k = cfg.ssm.slstm_every
        plan = []
        if k and cfg.n_layers >= k:
            groups = cfg.n_layers // k
            for _ in range(groups):
                plan.append(Segment("scan", k - 1, "mlstm", "none"))
                plan.append(Segment("single", 1, "slstm", "none"))
            rem = cfg.n_layers - groups * k
        else:
            rem = cfg.n_layers
        if rem:
            plan.append(Segment("scan", rem, "mlstm", "none"))
        return plan
    if cfg.family == "hybrid":                  # hymba
        gl = sorted(cfg.global_attn_layers)
        plan = []
        prev = 0
        for g in gl:
            if g > prev:
                plan.append(Segment("scan", g - prev, "hybrid", "swiglu",
                                    window=cfg.sliding_window))
            plan.append(Segment("single", 1, "hybrid", "swiglu", window=0))
            prev = g + 1
        if prev < cfg.n_layers:
            plan.append(Segment("scan", cfg.n_layers - prev, "hybrid", "swiglu",
                                window=cfg.sliding_window))
        return plan
    mixer = "mla" if cfg.mla is not None else "attn"
    cross = cfg.family == "audio"
    if cfg.moe is not None:
        plan = []
        if cfg.first_k_dense:
            plan.append(Segment("scan", cfg.first_k_dense, mixer, "swiglu", cross=cross))
        plan.append(Segment("scan", cfg.n_layers - cfg.first_k_dense, mixer,
                            "moe", cross=cross))
        return plan
    return [Segment("scan", cfg.n_layers, mixer, "swiglu", cross=cross)]


# ---------------------------------------------------------------------------
# layer init / apply
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ArchConfig, seg: Segment):
    ks = jax.random.split(key, 8)
    p = {"norm1": L.rmsnorm_init(cfg.d_model, cfg.dtype)}
    if seg.mixer in ("attn", "hybrid"):
        p["attn"] = A.gqa_init(ks[0], cfg)
    if seg.mixer == "hybrid":
        p["ssd"] = S.ssd_init(ks[1], cfg)
    if seg.mixer == "mla":
        p["attn"] = A.mla_init(ks[0], cfg)
    if seg.mixer == "mlstm":
        p["mixer"] = S.mlstm_init(ks[2], cfg)
    if seg.mixer == "slstm":
        p["mixer"] = S.slstm_init(ks[2], cfg)
    if seg.cross:
        p["normc"] = L.rmsnorm_init(cfg.d_model, cfg.dtype)
        p["cross"] = A.gqa_init(ks[3], cfg)
    if seg.ffn != "none":
        p["norm2"] = L.rmsnorm_init(cfg.d_model, cfg.dtype)
    if seg.ffn == "swiglu":
        p["ffn"] = L.swiglu_init(ks[4], cfg.d_model, cfg.d_ff, cfg.dtype)
    elif seg.ffn == "moe":
        p["ffn"] = M.moe_init(ks[4], cfg)
    return p


def _apply_mixer_seq(cfg, seg, lp, xn, positions, *, backend, want_cache,
                     smax=0, kv_quant=False, attn_constraint=None,
                     shardmap_attn=None):
    """Full-sequence mixer; returns (y, cache_leaf or None)."""
    if seg.mixer == "attn":
        if shardmap_attn is not None:
            y = shardmap_attn(lp["attn"], xn, positions, seg.window)
            if want_cache:
                # cache K/V via the plain projections (cheap vs attention)
                k = A._split_heads(L.linear(lp["attn"]["wk"], xn),
                                   cfg.n_kv_heads, cfg.hd)
                v = A._split_heads(L.linear(lp["attn"]["wv"], xn),
                                   cfg.n_kv_heads, cfg.hd)
                k = L.apply_rope(k, positions, cfg.rope_theta)
                return y, A.gqa_prefill_cache(cfg, smax, k, v, seg.window,
                                              quant=kv_quant)
            return y, None
        if want_cache:
            y, kv = A.gqa_forward(cfg, lp["attn"], xn, positions,
                                  window=seg.window, backend=backend,
                                  return_cache=True,
                                  attn_constraint=attn_constraint)
            return y, A.gqa_prefill_cache(cfg, smax, kv["k"], kv["v"],
                                          seg.window, quant=kv_quant)
        return A.gqa_forward(cfg, lp["attn"], xn, positions,
                             window=seg.window, backend=backend,
                             attn_constraint=attn_constraint), None
    if seg.mixer == "mla":
        if want_cache:
            y, c = A.mla_forward(cfg, lp["attn"], xn, positions,
                                 backend=backend, return_cache=True)
            return y, A.mla_prefill_cache(cfg, smax, c)
        return A.mla_forward(cfg, lp["attn"], xn, positions, backend=backend), None
    if seg.mixer == "hybrid":
        if want_cache:
            ya, kv = A.gqa_forward(cfg, lp["attn"], xn, positions,
                                   window=seg.window, backend=backend,
                                   return_cache=True)
            ys, st = S.ssd_forward(cfg, lp["ssd"], xn, backend=backend,
                                   return_state=True)
            cache = {"kv": A.gqa_prefill_cache(
                cfg, smax, kv["k"], kv["v"], seg.window, quant=kv_quant),
                "ssd": st}
            return 0.5 * (ya + ys), cache
        ya = A.gqa_forward(cfg, lp["attn"], xn, positions,
                           window=seg.window, backend=backend)
        ys = S.ssd_forward(cfg, lp["ssd"], xn, backend=backend)
        return 0.5 * (ya + ys), None
    if seg.mixer == "mlstm":
        if want_cache:
            return S.mlstm_forward(cfg, lp["mixer"], xn, backend=backend,
                                   return_state=True)
        return S.mlstm_forward(cfg, lp["mixer"], xn, backend=backend), None
    if seg.mixer == "slstm":
        if want_cache:
            return S.slstm_forward(cfg, lp["mixer"], xn, return_state=True)
        return S.slstm_forward(cfg, lp["mixer"], xn), None
    raise ValueError(seg.mixer)


def _apply_layer_seq(cfg, seg, lp, carry, positions, *, backend,
                     want_cache=False, smax=0, enc_out=None, enc_cache=False,
                     capacity_factor=1.25, kv_quant=False, act_constraint=None,
                     attn_constraint=None, shardmap_attn=None,
                     want_stats=False):
    """(x, aux) -> (x', aux'), cache_leaf; with ``want_stats`` the cache
    leaf is ``(cache_leaf, routing stats or None)``."""
    x, aux = carry
    if act_constraint is not None:
        x = jax.lax.with_sharding_constraint(x, act_constraint)
    xn = L.rmsnorm(lp["norm1"], x, cfg.norm_eps)
    y, cache = _apply_mixer_seq(cfg, seg, lp, xn, positions, backend=backend,
                                want_cache=want_cache, smax=smax,
                                kv_quant=kv_quant,
                                attn_constraint=attn_constraint,
                                shardmap_attn=shardmap_attn)
    x = x + y
    if seg.cross and enc_out is not None:
        xc = L.rmsnorm(lp["normc"], x, cfg.norm_eps)
        ck = A._split_heads(L.linear(lp["cross"]["wk"], enc_out),
                            cfg.n_kv_heads, cfg.hd)
        cv = A._split_heads(L.linear(lp["cross"]["wv"], enc_out),
                            cfg.n_kv_heads, cfg.hd)
        yc = A.gqa_forward(cfg, lp["cross"], xc, positions, causal=False,
                           backend=backend, kv_override=(ck, cv))
        x = x + yc
        if want_cache:
            cache = {"self": cache, "cross_k": ck, "cross_v": cv}
    st = None
    if seg.ffn == "swiglu":
        x = x + L.swiglu(lp["ffn"], L.rmsnorm(lp["norm2"], x, cfg.norm_eps))
    elif seg.ffn == "moe":
        y, a, *rest = M.moe_forward(cfg, lp["ffn"],
                                    L.rmsnorm(lp["norm2"], x, cfg.norm_eps),
                                    backend=backend,
                                    capacity_factor=capacity_factor,
                                    stats=want_stats)
        x = x + y
        aux = aux + a
        st = rest[0] if rest else None
    return (x, aux), ((cache, st) if want_stats else cache)


def _apply_layer_decode(cfg, seg, lp, carry, cache, pos, *, backend,
                        capacity_factor=2.0, kv_constraint=None):
    x, aux = carry
    self_cache = cache["self"] if seg.cross else cache
    xn = L.rmsnorm(lp["norm1"], x, cfg.norm_eps)
    if seg.mixer == "attn":
        y, new_cache = A.gqa_decode(cfg, lp["attn"], xn, self_cache, pos,
                                    window=seg.window, backend=backend,
                                    kv_constraint=kv_constraint)
    elif seg.mixer == "mla":
        y, new_cache = A.mla_decode(cfg, lp["attn"], xn, self_cache, pos,
                                    backend=backend)
    elif seg.mixer == "hybrid":
        ya, kv = A.gqa_decode(cfg, lp["attn"], xn, self_cache["kv"], pos,
                              window=seg.window, backend=backend)
        ys, st = S.ssd_decode(cfg, lp["ssd"], xn, self_cache["ssd"])
        y, new_cache = 0.5 * (ya + ys), {"kv": kv, "ssd": st}
    elif seg.mixer == "mlstm":
        y, new_cache = S.mlstm_decode(cfg, lp["mixer"], xn, self_cache)
    elif seg.mixer == "slstm":
        y, new_cache = S.slstm_decode(cfg, lp["mixer"], xn, self_cache)
    else:
        raise ValueError(seg.mixer)
    x = x + y
    if seg.cross:
        # cross-attend to the cached encoder K/V (computed at prefill)
        ck, cv = cache["cross_k"], cache["cross_v"]
        xc = L.rmsnorm(lp["normc"], x, cfg.norm_eps)
        yc = A.gqa_forward(cfg, lp["cross"], xc,
                           jnp.zeros((x.shape[0], 1), jnp.int32),
                           causal=False, backend=backend, kv_override=(ck, cv))
        x = x + yc
        new_cache = {"self": new_cache, "cross_k": ck, "cross_v": cv}
    if seg.ffn == "swiglu":
        x = x + L.swiglu(lp["ffn"], L.rmsnorm(lp["norm2"], x, cfg.norm_eps))
    elif seg.ffn == "moe":
        y, a = M.moe_forward(cfg, lp["ffn"],
                             L.rmsnorm(lp["norm2"], x, cfg.norm_eps),
                             backend=backend, capacity_factor=capacity_factor)
        x = x + y
        aux = aux + a
    return (x, aux), new_cache


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class Model:
    """Decoder LM / enc-dec / VLM backbone built from an ArchConfig."""

    def __init__(self, cfg: ArchConfig, backend: Optional[str] = None,
                 capacity_factor: Optional[float] = None, unroll: bool = False,
                 kv_quant: bool = False, act_constraint=None):
        self.cfg = cfg
        self.backend = backend
        self.capacity_factor = capacity_factor   # None -> mode defaults
        # Perf levers (EXPERIMENTS.md section Perf): int8 KV cache; explicit
        # activation sharding constraint (NamedSharding) at block boundaries.
        self.kv_quant = kv_quant
        self.act_constraint = act_constraint
        self.kv_update_constraint = None   # A2 lever: shard-local cache writes
        self.attn_layout_constraint = None  # B4 lever: head-sharded flash layout
        self.shardmap_attn = None           # B5 lever: explicit shard_map mixer
        # unroll=True applies scanned segments layer-by-layer (same stacked
        # param/cache layout). The dry-run uses it so XLA cost analysis sees
        # every layer (while-loop bodies are costed once, not x trip-count).
        self.unroll = unroll
        self.plan = build_plan(cfg)

    def _cf(self, default: float) -> float:
        return self.capacity_factor if self.capacity_factor is not None else default

    # ------------------------------------------------------------- params
    def init_params(self, key):
        cfg = self.cfg
        keys = jax.random.split(key, len(self.plan) + 4)
        segs = []
        for i, seg in enumerate(self.plan):
            if seg.kind == "scan":
                lkeys = jax.random.split(keys[i], seg.n)
                segs.append(jax.vmap(lambda k: _layer_init(k, cfg, seg))(lkeys))
            else:
                segs.append(_layer_init(keys[i], cfg, seg))
        params = {
            "embed": L.embedding_init(keys[-1], cfg.vocab_size, cfg.d_model, cfg.dtype),
            "final_norm": L.rmsnorm_init(cfg.d_model, cfg.dtype),
            "segments": segs,
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.linear_init(keys[-2], cfg.d_model,
                                              cfg.vocab_size, cfg.dtype)
        if cfg.encoder_layers:
            ekeys = jax.random.split(keys[-3], 2)
            eseg = Segment("scan", cfg.encoder_layers, "attn", "swiglu")
            elkeys = jax.random.split(ekeys[0], cfg.encoder_layers)
            params["encoder"] = {
                "layers": jax.vmap(lambda k: _layer_init(k, cfg, eseg))(elkeys),
                "final_norm": L.rmsnorm_init(cfg.d_model, cfg.dtype),
            }
        return params

    # ------------------------------------------------------------ helpers
    def _logits(self, params, x):
        x = L.rmsnorm(params["final_norm"], x, self.cfg.norm_eps)
        if self.cfg.tie_embeddings:
            return L.unembed(params["embed"], x)
        return L.linear(params["lm_head"], x).astype(jnp.float32)

    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        x = L.embed(params["embed"], batch["tokens"])
        if cfg.vision_tokens and "vision_embeds" in batch:
            vis = batch["vision_embeds"].astype(x.dtype)
            x = jnp.concatenate([vis, x[:, cfg.vision_tokens:]], axis=1)
        return x

    def _encode(self, params, frames):
        """Encoder stack over stub frame embeddings (audio frontend stub)."""
        cfg = self.cfg
        enc = params["encoder"]
        positions = jnp.arange(frames.shape[1])[None, :]
        eseg = Segment("scan", cfg.encoder_layers, "attn", "swiglu")

        def body(carry, lp):
            x, aux = carry
            xn = L.rmsnorm(lp["norm1"], x, cfg.norm_eps)
            y = A.gqa_forward(cfg, lp["attn"], xn, positions, causal=False,
                              backend=self.backend)
            x = x + y
            x = x + L.swiglu(lp["ffn"], L.rmsnorm(lp["norm2"], x, cfg.norm_eps))
            return (x, aux), None

        fn = jax.checkpoint(body) if cfg.remat else body
        (x, _), _ = jax.lax.scan(fn, (frames.astype(jnp.dtype(cfg.dtype)), 0.0),
                                 enc["layers"])
        return L.rmsnorm(enc["final_norm"], x, cfg.norm_eps)

    def _backbone_seq(self, params, x, positions, *, want_cache, smax,
                      enc_out=None, want_stats=False):
        """-> (carry, caches); with ``want_stats`` each segment's entry of
        ``caches`` is ``(cache, routing stats or None)``."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        caches = []
        carry = (x, aux)
        for seg, sp in zip(self.plan, params["segments"]):
            if seg.kind == "scan":
                sp, whole = _split_experts(cfg, seg, sp)

                def body(c, lp, seg=seg, whole=whole):
                    if whole is not None:
                        lp = _with_experts(*lp, whole)
                    c2, cache = _apply_layer_seq(
                        cfg, seg, lp, c, positions, backend=self.backend,
                        want_cache=want_cache, smax=smax, enc_out=enc_out,
                        enc_cache=True, capacity_factor=self._cf(1.25),
                        kv_quant=self.kv_quant,
                        act_constraint=self.act_constraint,
                        attn_constraint=self.attn_layout_constraint,
                        shardmap_attn=self.shardmap_attn,
                        want_stats=want_stats)
                    return c2, cache
                fn = jax.checkpoint(body) if cfg.remat else body
                if whole is not None:
                    sp = (sp, jnp.arange(seg.n))
                if self.unroll:
                    carry, seg_cache = _unrolled_scan(fn, carry, sp, seg.n)
                else:
                    carry, seg_cache = jax.lax.scan(fn, carry, sp)
            else:
                carry, seg_cache = _apply_layer_seq(
                    cfg, seg, sp, carry, positions, backend=self.backend,
                    want_cache=want_cache, smax=smax, enc_out=enc_out,
                    enc_cache=True, capacity_factor=self._cf(1.25),
                    kv_quant=self.kv_quant,
                    act_constraint=self.act_constraint,
                    attn_constraint=self.attn_layout_constraint,
                    shardmap_attn=self.shardmap_attn, want_stats=want_stats)
            caches.append(seg_cache)
        return carry, caches

    # -------------------------------------------------------------- train
    def train_loss(self, params, batch):
        """batch: tokens (B,S), labels (B,S) [+ frames / vision_embeds]."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        enc_out = None
        if cfg.encoder_layers:
            enc_out = self._encode(params, batch["frames"])
        (x, aux), _ = self._backbone_seq(params, x, positions,
                                         want_cache=False, smax=0,
                                         enc_out=enc_out)
        logits = self._logits(params, x)
        loss = L.cross_entropy(logits[:, :-1], batch["labels"][:, 1:])
        return loss + 0.01 * aux, {"loss": loss, "aux": aux}

    # ------------------------------------------------------------ prefill
    def prefill(self, params, batch, smax: int, stats: bool = False):
        """-> (logits at the last position, caches); with ``stats`` also
        the expert layers' routing stats (``routing_stats``), or None for a
        model without them."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        enc_out = None
        if cfg.encoder_layers:
            enc_out = self._encode(params, batch["frames"])
        (x, _), caches = self._backbone_seq(params, x, positions,
                                            want_cache=True, smax=smax,
                                            enc_out=enc_out, want_stats=stats)
        logits = self._logits(params, x[:, -1:])
        if not stats:
            return logits, caches
        return logits, [c for c, _ in caches], routing_stats(
            [st for _, st in caches])

    def prefill_batch(self, params, batch, smax: int):
        """Batched ragged prefill for the serving engine's batched
        admission: one padded forward over B right-padded prompts.

        ``batch``: ``tokens`` (B, S) int32 right-padded, ``lengths`` (B,)
        int32 true prompt lengths.  Returns ``(logits (B, 1, V), caches)``
        where row ``i``'s logits are taken at position ``lengths[i]-1``
        (the last *real* token, not the padded tail).  Cache positions
        beyond a row's length hold pad-token K/V -- the same contamination
        class as the pool's zero rows, tolerated because decode attends
        under a causal mask up to the row's own length.
        """
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        positions = jnp.arange(x.shape[1])[None, :]
        enc_out = None
        if cfg.encoder_layers:
            enc_out = self._encode(params, batch["frames"])
        (x, _), caches = self._backbone_seq(params, x, positions,
                                            want_cache=True, smax=smax,
                                            enc_out=enc_out)
        last = (batch["lengths"].astype(jnp.int32) - 1)[:, None, None]
        idx = jnp.broadcast_to(last, (x.shape[0], 1, x.shape[2]))
        x_last = jnp.take_along_axis(x, idx, axis=1)       # (B, 1, D)
        logits = self._logits(params, x_last)
        return logits, caches

    # ------------------------------------------------------------- decode
    def decode_step(self, params, caches, token, pos):
        """token: (B, 1) int32; pos: traced scalar; caches from prefill."""
        cfg = self.cfg
        x = L.embed(params["embed"], token)
        aux = jnp.zeros((), jnp.float32)
        carry = (x, aux)
        new_caches = []
        for seg, sp, sc in zip(self.plan, params["segments"], caches):
            if seg.kind == "scan":
                sp, whole = _split_experts(cfg, seg, sp)
                if whole is not None:
                    sp = (sp, jnp.arange(seg.n))

                def body(c, xs, seg=seg, whole=whole):
                    lp, cache = xs
                    if whole is not None:
                        lp = _with_experts(*lp, whole)
                    c2, nc = _apply_layer_decode(
                        cfg, seg, lp, c, cache, pos, backend=self.backend,
                        capacity_factor=self._cf(2.0),
                        kv_constraint=self.kv_update_constraint)
                    return c2, nc
                if self.unroll:
                    carry, seg_cache = _unrolled_scan(body, carry, (sp, sc), seg.n)
                else:
                    carry, seg_cache = jax.lax.scan(body, carry, (sp, sc))
            else:
                carry, seg_cache = _apply_layer_decode(
                    cfg, seg, sp, carry, sc, pos, backend=self.backend,
                    capacity_factor=self._cf(2.0),
                    kv_constraint=self.kv_update_constraint)
            new_caches.append(seg_cache)
        logits = self._logits(params, carry[0])
        return logits, new_caches

    # ---------------------------------------------------------- cache spec
    def init_cache(self, batch_size: int, smax: int, dtype=None):
        """Zero caches (or use shapes for ShapeDtypeStruct via tree_map)."""
        cfg = self.cfg
        dt = jnp.dtype(dtype or cfg.dtype)
        caches = []
        for seg in self.plan:
            leaf = self._seg_cache_leaf(seg, batch_size, smax, dt)
            if seg.kind == "scan":
                leaf = jax.tree.map(
                    lambda a: jnp.zeros((seg.n,) + a.shape, a.dtype), leaf)
            caches.append(leaf)
        return caches

    def cache_pspecs(self, mesh, batch_size: int, smax: int):
        """PartitionSpec tree matching init_cache: batch over data axes
        (sequence for batch-1 long context), head dims over model. The
        leading layer dim of scanned segments is never sharded."""
        shapes = jax.eval_shape(lambda: self.init_cache(batch_size, smax))
        out = []
        for seg, seg_shapes in zip(self.plan, shapes):
            scanned = seg.kind == "scan"
            out.append(jax.tree_util.tree_map_with_path(
                lambda path, leaf, seg=seg, sc=scanned:
                    _cache_leaf_pspec(seg, path, leaf.shape, mesh, sc),
                seg_shapes))
        return out

    def _seg_cache_leaf(self, seg: Segment, b: int, smax: int, dt):
        cfg = self.cfg
        kh, hd = cfg.n_kv_heads, cfg.hd
        h = cfg.n_heads
        if seg.mixer == "attn":
            s = seg.window if seg.window else smax
            if self.kv_quant:
                leaf = {"k": jnp.zeros((b, s, kh, hd), jnp.int8),
                        "v": jnp.zeros((b, s, kh, hd), jnp.int8),
                        "k_scale": jnp.zeros((b, s, kh), jnp.float32),
                        "v_scale": jnp.zeros((b, s, kh), jnp.float32)}
            else:
                leaf = {"k": jnp.zeros((b, s, kh, hd), dt),
                        "v": jnp.zeros((b, s, kh, hd), dt)}
        elif seg.mixer == "mla":
            m = cfg.mla
            leaf = {"c": jnp.zeros((b, smax, m.kv_lora_rank), dt),
                    "kr": jnp.zeros((b, smax, m.qk_rope_head_dim), dt)}
        elif seg.mixer == "hybrid":
            s = seg.window if seg.window else smax
            n = cfg.ssm.state_dim
            leaf = {"kv": {"k": jnp.zeros((b, s, kh, hd), dt),
                           "v": jnp.zeros((b, s, kh, hd), dt)},
                    "ssd": {"c": jnp.zeros((b, h, n, hd), jnp.float32),
                            "n": jnp.zeros((b, h, n), jnp.float32)}}
        elif seg.mixer == "mlstm":
            di = cfg.ssm.expand * cfg.d_model
            hdm = di // h
            leaf = {"c": jnp.zeros((b, h, hdm, hdm), jnp.float32),
                    "n": jnp.zeros((b, h, hdm), jnp.float32)}
        elif seg.mixer == "slstm":
            leaf = {"c": jnp.zeros((b, cfg.d_model), jnp.float32),
                    "n": jnp.zeros((b, cfg.d_model), jnp.float32)}
        else:
            raise ValueError(seg.mixer)
        if seg.cross:
            leaf = {"self": leaf,
                    "cross_k": jnp.zeros((b, cfg.encoder_len, kh, hd), dt),
                    "cross_v": jnp.zeros((b, cfg.encoder_len, kh, hd), dt)}
        return leaf


def _split_experts(cfg, seg: Segment, sp):
    """Held-expert weights (sigmoid form, models/moe.py) stay whole across
    the layer scan: a scanned slice of them would be copied out for the
    grouped products in every layer, every held expert read and written.
    Returns (the segment's other params, whole expert weights or None)."""
    if seg.ffn != "moe" or cfg.moe.scoring != "sigmoid":
        return sp, None
    ffn = dict(sp["ffn"])
    whole = ffn.pop("experts")
    return dict(sp, ffn=ffn), whole


def _with_experts(lp, layer, whole):
    """One layer's params, given the whole expert stack and its index."""
    return dict(lp, ffn=dict(lp["ffn"], experts=whole, layer=layer))


def routing_stats(per_segment: list):
    """Sum the routed and held assignments over the expert layers and take
    the most any held expert took in one layer; None without such layers."""
    st = [x for x in per_segment if x is not None]
    if not st:
        return None
    return {"assignments": sum(jnp.sum(x["assignments"]) for x in st),
            "held": sum(jnp.sum(x["held"]) for x in st),
            "held_max": functools.reduce(jnp.maximum,
                                         [jnp.max(x["held_max"]) for x in st])}


def _unrolled_scan(body, carry, xs, n):
    """Python-level scan (same semantics as lax.scan, stacked xs/ys)."""
    ys = []
    for i in range(n):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def _cache_leaf_pspec(seg: Segment, leaf_path: tuple, shape: tuple,
                      mesh, scanned: bool):
    """PartitionSpec for one cache leaf (see Model.cache_pspecs)."""
    from jax.sharding import PartitionSpec as P
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    tp = mesh.shape["model"] if "model" in names else 1
    off = 1 if scanned else 0
    spec = [None] * len(shape)
    b = shape[off]
    if b >= dp_size and b % dp_size == 0 and b > 1:
        spec[off] = dp if len(dp) > 1 else dp[0]
    elif len(shape) > off + 1:
        # long-context batch-1 decode: shard the sequence dim instead
        s_dim = off + 1
        if shape[s_dim] >= 4096 and shape[s_dim] % dp_size == 0:
            spec[s_dim] = dp if len(dp) > 1 else dp[0]
    # shard a heads-like dim over model if it divides
    for d in range(len(shape) - 2, off, -1):
        if spec[d] is None and tp > 1 and shape[d] % tp == 0 and shape[d] >= tp:
            spec[d] = "model"
            break
    return P(*spec)


def make_model(name_or_cfg, backend: Optional[str] = None) -> Model:
    from ..configs.base import get_arch
    cfg = name_or_cfg if isinstance(name_or_cfg, ArchConfig) else get_arch(name_or_cfg)
    return Model(cfg, backend=backend)
