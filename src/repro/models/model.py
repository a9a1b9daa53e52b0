"""Composable model: the 10 assigned architectures behind one API.

A model is a repeating period of sublayers scanned over groups (see
blocks.py).  Three entry points:

- ``forward``      : full-sequence (train / prefill), optional cache return
- ``decode_step``  : one token against a KV/SSM cache (serving)
- ``encode``       : whisper encoder (frame embeddings -> memory)

Caches are pytrees with a leading group dim so decode also scans.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs import ArchConfig
from repro.models import attention as A
from repro.models import blocks as B
from repro.models import mamba as M
from repro.models.layers import norm, sinusoidal_positions
from repro.models.params import (ParamDesc, init_params, param_pspecs,
                                 param_shapes, stack_tree)
from repro.sharding.specs import AxisRules, batch_axes, constrain

Tree = Any


class Model:
    def __init__(self, cfg: ArchConfig, rules: Optional[AxisRules] = None, *,
                 q_chunk: int = 1024, kv_chunk: int = 2048,
                 remat: bool = False):
        self.cfg = cfg
        self.rules = rules or AxisRules()
        self.q_chunk = q_chunk
        self.kv_chunk = kv_chunk
        self.remat = remat
        p_len = len(cfg.layer_period)
        assert cfg.num_layers % p_len == 0, (cfg.name, cfg.num_layers, p_len)
        self.period = cfg.layer_period
        self.n_groups = cfg.num_layers // p_len
        self.attn_pos = [i for i, k in enumerate(self.period) if k == "attn"]
        self.mamba_pos = [i for i, k in enumerate(self.period) if k == "mamba"]
        self.is_encdec = cfg.encoder is not None
        self.use_rope = cfg.norm_kind != "layernorm" or not self.is_encdec
        # whisper (layernorm + encdec) uses sinusoidal absolute positions
        self.absolute_pos = self.is_encdec

    # ------------------------------------------------------------------
    # Params
    # ------------------------------------------------------------------

    def param_descs(self) -> Tree:
        cfg, rules = self.cfg, self.rules
        tp = rules.tensor_axis
        vshard = tp if rules.mesh is None or rules.divisible(cfg.vocab, tp) else None
        descs: Dict[str, Any] = {
            "embed": ParamDesc((cfg.vocab, cfg.d_model), P(vshard, None)),
            "groups": stack_tree(
                B.sublayer_descs(cfg, rules, with_cross=self.is_encdec),
                self.n_groups),
            "final_norm": B.norm_descs(cfg),
        }
        if not cfg.tie_embeddings:
            descs["lm_head"] = ParamDesc((cfg.d_model, cfg.vocab), P(None, vshard))
        if cfg.vision is not None:
            descs["vision_proj"] = ParamDesc(
                (cfg.vision.embed_dim, cfg.d_model), P(None, None))
        if self.is_encdec:
            enc_layer = {
                "attn_norm": B.norm_descs(cfg),
                "attn": A.attn_param_descs(cfg, rules),
                "ffn_norm": B.norm_descs(cfg),
                "ffn": B.mlp_param_descs(cfg, rules),
            }
            descs["encoder"] = {
                "layers": stack_tree(enc_layer, cfg.encoder.num_layers),
                "final_norm": B.norm_descs(cfg),
            }
        return descs

    def init(self, rng: jax.Array, dtype=jnp.float32) -> Tree:
        return init_params(self.param_descs(), rng, dtype)

    def shapes(self, dtype=jnp.bfloat16) -> Tree:
        return param_shapes(self.param_descs(), dtype)

    def pspecs(self) -> Tree:
        return param_pspecs(self.param_descs())

    # ------------------------------------------------------------------
    # Encoder (whisper)
    # ------------------------------------------------------------------

    def encode(self, params: Tree, frames: jax.Array) -> jax.Array:
        """frames: (B, src_len, d_model) precomputed conv/mel embeddings."""
        cfg, rules = self.cfg, self.rules
        x = frames + sinusoidal_positions(frames.shape[1], cfg.d_model
                                          ).astype(frames.dtype)
        positions = jnp.arange(frames.shape[1])

        def body(x, lp):
            h = norm(x, lp["attn_norm"], cfg.norm_kind, cfg.norm_eps)
            y, _ = self._attn(lp["attn"], h, positions, causal=False)
            x = x + y
            h = norm(x, lp["ffn_norm"], cfg.norm_kind, cfg.norm_eps)
            return x + B.mlp_forward(lp["ffn"], h, cfg, rules), None

        x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
        return norm(x, params["encoder"]["final_norm"], cfg.norm_kind, cfg.norm_eps)

    def _attn(self, p, h, positions, *, causal=True, prefix_len=0):
        """Self-attention returning (out, (k_rot, v)) for cache building."""
        cfg, rules = self.cfg, self.rules
        from repro.models.attention import _project_qkv, _out_proj
        from repro.models.layers import apply_rope, gqa_attention
        seq = rules.seq_axis if h.shape[1] > 1 else None
        win = cfg.sliding_window if causal else None
        if seq is not None and causal:
            return self._attn_seq_parallel(p, h, prefix_len=prefix_len,
                                           window=win)
        q, k, v = _project_qkv(p, h)
        hs = rules.tensor_axis if (rules.mesh is None or rules.divisible(
            cfg.num_heads, rules.tensor_axis)) else None
        q = constrain(q, rules, P(batch_axes(rules), None, hs, None))
        if self.use_rope:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        o = gqa_attention(q, k, v, positions, positions, causal=causal,
                          window=win, prefix_len=prefix_len,
                          q_chunk=self.q_chunk, kv_chunk=self.kv_chunk)
        return _out_proj(p, o, rules), (k, v)

    def _attn_seq_parallel(self, p, h, *, prefix_len=0, window=None):
        """Sequence-parallel attention sublayer (§Perf C): the whole sublayer
        runs inside shard_map so the sequence-sharded hidden never leaves its
        shard — XLA gathers the (far smaller) projection weights, and only
        the GQA-small K/V are all-gathered across sequence shards."""
        cfg, rules = self.cfg, self.rules
        from repro.models.layers import apply_rope, gqa_attention
        mesh = rules.mesh
        seq = rules.seq_axis
        nsh = rules.axis_size(seq)
        s_full = h.shape[1]
        sl = s_full // nsh
        ba = batch_axes(rules)
        qc, kc = self.q_chunk, self.kv_chunk
        use_rope = self.use_rope
        theta = cfg.rope_theta
        has_bias = "bq" in p

        def body(hl, wq, wk, wv, wo, *bias):
            i = jax.lax.axis_index(seq)
            qpos = i * sl + jnp.arange(sl)
            kpos = jnp.arange(s_full)
            ql = jnp.einsum("bsd,dhk->bshk", hl, wq)
            kl = jnp.einsum("bsd,dhk->bshk", hl, wk)
            vl = jnp.einsum("bsd,dhk->bshk", hl, wv)
            if has_bias:
                bq, bk, bv = bias
                ql, kl, vl = ql + bq, kl + bk, vl + bv
            if use_rope:
                ql = apply_rope(ql, qpos, theta)
                kl = apply_rope(kl, qpos, theta)   # local slice positions
            kf = jax.lax.all_gather(kl, seq, axis=1, tiled=True)
            vf = jax.lax.all_gather(vl, seq, axis=1, tiled=True)
            o = gqa_attention(ql, kf, vf, qpos, kpos, causal=True,
                              window=window, prefix_len=prefix_len,
                              q_chunk=qc, kv_chunk=kc)
            y = jnp.einsum("bshk,hkd->bsd", o, wo)
            return y, kl, vl

        rep2 = P(None, None)
        args = [p["wq"], p["wk"], p["wv"], p["wo"]]
        in_specs = [P(ba, seq, None), P(None, None, None), P(None, None, None),
                    P(None, None, None), P(None, None, None)]
        if has_bias:
            args += [p["bq"], p["bk"], p["bv"]]
            in_specs += [rep2, rep2, rep2]
        y, k, v = shard_map(
            body, mesh=mesh,
            in_specs=tuple([in_specs[0]] + in_specs[1:]),
            out_specs=(P(ba, seq, None), P(ba, seq, None, None),
                       P(ba, seq, None, None)),
            check_vma=False)(h, *args)
        return y, (k, v)

    # ------------------------------------------------------------------
    # Forward (train / prefill)
    # ------------------------------------------------------------------

    def forward(self, params: Tree, tokens: jax.Array, *,
                patches: Optional[jax.Array] = None,
                frames: Optional[jax.Array] = None,
                return_cache: bool = False,
                cache_len: Optional[int] = None,
                last_logit_only: bool = False
                ) -> Tuple[jax.Array, jax.Array, Optional[Tree]]:
        """tokens: (B, S_text). Returns (logits (B,S,V), moe_aux, cache)."""
        cfg, rules = self.cfg, self.rules
        x = jnp.take(params["embed"], tokens, axis=0)
        prefix_len = 0
        if cfg.vision is not None:
            assert patches is not None
            pre = jnp.einsum("bpe,ed->bpd", patches.astype(x.dtype),
                             params["vision_proj"])
            x = jnp.concatenate([pre, x], axis=1)
            prefix_len = patches.shape[1]
        enc_out = None
        if self.is_encdec:
            assert frames is not None
            enc_out = self.encode(params, frames)
        S = x.shape[1]
        positions = jnp.arange(S)
        if self.absolute_pos:
            x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)
        seq = rules.seq_axis if S % max(rules.axis_size(
            rules.seq_axis or rules.tensor_axis), 1) == 0 and \
            rules.seq_axis is not None else None
        x = constrain(x, rules, P(batch_axes(rules), seq, None))

        collect = return_cache

        def group_body(carry, gp):
            x, aux = carry
            ys = {"k": [], "v": [], "h": [], "conv": [], "ck": [], "cv": []}
            for i, kind in enumerate(self.period):
                sub = gp[f"pos{i}"]
                h = norm(x, sub["mixer_norm"], cfg.norm_kind, cfg.norm_eps)
                if kind == "attn":
                    y, (k, v) = self._attn(sub["mixer"], h, positions,
                                           prefix_len=prefix_len)
                    if collect:
                        ys["k"].append(k)
                        ys["v"].append(v)
                    x = x + y
                    if self.is_encdec:
                        h = norm(x, sub["cross_norm"], cfg.norm_kind, cfg.norm_eps)
                        x = x + A.cross_attn_forward(sub["cross"], h, enc_out,
                                                     cfg, rules)
                        if collect:
                            cc = A.cross_attn_cache(sub["cross"], enc_out)
                            ys["ck"].append(cc["k"])
                            ys["cv"].append(cc["v"])
                else:
                    y, (hl, cs) = M.mamba_forward(sub["mixer"], h, cfg, rules,
                                                  return_state=True)
                    if collect:
                        ys["h"].append(hl)
                        ys["conv"].append(cs)
                    x = x + y
                x, a = B.apply_ffn(sub, x, cfg, rules, i)
                aux = aux + a
            out_ys = {k2: jnp.stack(v2) for k2, v2 in ys.items() if v2}
            return (x, aux), out_ys

        body = group_body
        if self.remat:
            body = jax.checkpoint(group_body,
                                  policy=jax.checkpoint_policies.nothing_saveable)
        (x, aux), ys = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                    params["groups"])
        x = norm(x, params["final_norm"], cfg.norm_kind, cfg.norm_eps)
        if last_logit_only:
            x = x[:, -1:]     # prefill: only the next-token logits matter
        logits = self._lm_head(params, x)
        cache = None
        if return_cache:
            cache = self._build_cache(ys, positions, S, cache_len, x.shape[0])
        return logits, aux, cache

    def _lm_head(self, params, x):
        w = params.get("lm_head")
        if w is None:
            w = params["embed"].T
        logits = jnp.einsum("bsd,dv->bsv", x, w)
        return constrain(logits, self.rules,
                         P(batch_axes(self.rules), None,
                           self.rules.tensor_axis
                           if self.rules.mesh is None
                           or self.rules.divisible(self.cfg.vocab,
                                                   self.rules.tensor_axis)
                           else None))

    # ------------------------------------------------------------------
    # Cache
    # ------------------------------------------------------------------

    def cache_len(self, seq_len: int) -> int:
        return A.kv_cache_len(self.cfg, seq_len)

    def cache_shapes(self, batch: int, seq_len: int, *,
                     dtype=jnp.bfloat16) -> Tree:
        cfg = self.cfg
        C = self.cache_len(seq_len)
        g = self.n_groups
        na, nm = len(self.attn_pos), len(self.mamba_pos)
        kh, hd = max(cfg.num_kv_heads, 1), cfg.hd
        d_in = (cfg.ssm.expand * cfg.d_model) if cfg.ssm else 1
        n_state = cfg.ssm.d_state if cfg.ssm else 1
        d_conv = cfg.ssm.d_conv if cfg.ssm else 2
        shapes: Dict[str, Any] = {"pos": jax.ShapeDtypeStruct((batch,), jnp.int32)}
        if na:
            shapes["k"] = jax.ShapeDtypeStruct((g, na, batch, C, kh, hd), dtype)
            shapes["v"] = jax.ShapeDtypeStruct((g, na, batch, C, kh, hd), dtype)
        if nm:
            shapes["h"] = jax.ShapeDtypeStruct((g, nm, batch, d_in, n_state),
                                               jnp.float32)
            shapes["conv"] = jax.ShapeDtypeStruct((g, nm, batch, d_conv - 1, d_in),
                                                  dtype)
        if self.is_encdec and na:
            src = cfg.encoder.src_len
            shapes["ck"] = jax.ShapeDtypeStruct((g, na, batch, src, kh, hd), dtype)
            shapes["cv"] = jax.ShapeDtypeStruct((g, na, batch, src, kh, hd), dtype)
        return shapes

    def cache_pspecs(self, batch: int, seq_len: int) -> Tree:
        """Sharding for the decode cache.

        KV heads shard over ``model`` when divisible; otherwise the cache
        *sequence* dim is context-parallel over ``model`` (XLA partitions
        the decode softmax with a small all-reduce) — essential for e.g.
        qwen3 (kv=4) whose 32k cache would not fit data-sharded only.
        When the batch itself can't shard (long_500k B=1) the sequence dim
        additionally takes the data axes."""
        rules = self.rules
        cfg = self.cfg
        tp = rules.tensor_axis
        C = self.cache_len(seq_len)
        ba = batch_axes(rules)
        b_ok = rules.mesh is None or batch % max(rules.axis_size(ba), 1) == 0
        bs = ba if b_ok else None
        kvs = tp if (rules.mesh is None or
                     rules.divisible(max(cfg.num_kv_heads, 1), tp)) else None
        if kvs is not None:
            seq_s = None
        else:
            cand = tp if b_ok else (tuple(rules.data_axes) + (tp,))
            seq_s = cand if (rules.mesh is None or
                             C % max(rules.axis_size(cand), 1) == 0) else None
        shapes = {"pos": P(bs)}
        if self.attn_pos:
            shapes["k"] = P(None, None, bs, seq_s, kvs, None)
            shapes["v"] = P(None, None, bs, seq_s, kvs, None)
        if self.mamba_pos:
            shapes["h"] = P(None, None, bs, tp, None)
            shapes["conv"] = P(None, None, bs, None, tp)
        if self.is_encdec and self.attn_pos:
            shapes["ck"] = P(None, None, bs, None, kvs, None)
            shapes["cv"] = P(None, None, bs, None, kvs, None)
        return shapes

    def init_cache(self, batch: int, seq_len: int, *, dtype=jnp.bfloat16) -> Tree:
        return jax.tree.map(lambda s: jnp.full(s.shape, -1, s.dtype)
                            if s.dtype == jnp.int32 else jnp.zeros(s.shape, s.dtype),
                            self.cache_shapes(batch, seq_len, dtype=dtype))

    def _build_cache(self, ys: Dict, positions, S: int,
                     cache_len: Optional[int], batch: int) -> Tree:
        """Convert scan-collected full-seq K/V + states into a decode cache."""
        C = self.cache_len(cache_len or S)
        cache: Dict[str, Any] = {}
        if "k" in ys:
            k, v = ys["k"], ys["v"]       # (G, na, B, S, KH, hd)
            if S > C:                      # keep last C (rotating slots)
                sl = slice(S - C, S)
                slots = jnp.arange(S - C, S) % C
                k = jnp.take(k[:, :, :, sl], jnp.argsort(slots), axis=3)
                v = jnp.take(v[:, :, :, sl], jnp.argsort(slots), axis=3)
            elif S < C:
                pad = [(0, 0)] * 6
                pad[3] = (0, C - S)
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            cache["k"], cache["v"] = k, v
        if "h" in ys:
            cache["h"] = ys["h"].astype(jnp.float32)
            cache["conv"] = ys["conv"]
        if "ck" in ys:
            cache["ck"], cache["cv"] = ys["ck"], ys["cv"]
        cache["pos"] = jnp.full((batch,), S, jnp.int32)
        return cache

    # ------------------------------------------------------------------
    # Decode
    # ------------------------------------------------------------------

    def decode_step(self, params: Tree, cache: Tree, tokens: jax.Array
                    ) -> Tuple[jax.Array, Tree]:
        """tokens: (B, 1) -> (logits (B, V), updated cache)."""
        cfg, rules = self.cfg, self.rules
        pos = cache["pos"]                                  # (B,)
        x = jnp.take(params["embed"], tokens, axis=0)
        if self.absolute_pos:
            pe = sinusoidal_positions(1 << 16, cfg.d_model)
            x = x + pe[pos][:, None].astype(x.dtype)
        x = constrain(x, rules, P(batch_axes(rules), None, None))

        xs = {"gp": params["groups"]}
        for key in ("k", "v", "h", "conv", "ck", "cv"):
            if key in cache:
                xs[key] = cache[key]

        def group_body(x, sl):
            gp = sl["gp"]
            new = {k2: [] for k2 in ("k", "v", "h", "conv")}
            ia = im = 0
            for i, kind in enumerate(self.period):
                sub = gp[f"pos{i}"]
                h = norm(x, sub["mixer_norm"], cfg.norm_kind, cfg.norm_eps)
                if kind == "attn":
                    y, kc, vc = A.attn_decode_step(
                        sub["mixer"], h, pos, sl["k"][ia], sl["v"][ia],
                        cfg, rules, use_rope=self.use_rope)
                    new["k"].append(kc)
                    new["v"].append(vc)
                    x = x + y
                    if self.is_encdec:
                        h = norm(x, sub["cross_norm"], cfg.norm_kind, cfg.norm_eps)
                        x = x + A.cross_attn_decode(
                            sub["cross"], h,
                            {"k": sl["ck"][ia], "v": sl["cv"][ia]}, rules)
                    ia += 1
                else:
                    y, hn, cn = M.mamba_decode_step(
                        sub["mixer"], h, sl["h"][im], sl["conv"][im], cfg, rules)
                    new["h"].append(hn)
                    new["conv"].append(cn)
                    x = x + y
                    im += 1
                x, _ = B.apply_ffn(sub, x, cfg, rules, i)
            ys = {k2: jnp.stack(v2) for k2, v2 in new.items() if v2}
            return x, ys

        x, ys = jax.lax.scan(group_body, x, xs)
        x = norm(x, params["final_norm"], cfg.norm_kind, cfg.norm_eps)
        logits = self._lm_head(params, x)[:, 0]
        out_cache = dict(cache)
        out_cache.update(ys)
        out_cache["pos"] = pos + 1
        return logits, out_cache
